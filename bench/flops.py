"""Operations per row of the plain reference, as XLA's cost analysis
counts them: the work a share of the chip's peak is read against.

    python3 bench/flops.py configs/siren-image.json   # print the counts

The counts are recorded as data in each configuration file
(``flops_per_row``), so nothing the program does can move the work a
share is read against; a test recomputes them.  Counted on the CPU
backend over ``ROWS`` rows, divided by ``ROWS``.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROWS = 64
BENCH = Path(__file__).resolve().parent


def count(fn, *shapes) -> float:
    import jax
    compiled = jax.jit(fn).lower(*shapes).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return float(cost["flops"])


def flops_per_row(cfg: dict, ref) -> dict:
    """``tower_o<n>``: the order-n tower (image configurations: n = 1..3;
    every configuration: n = 1); ``grad_mse_vg_o1``: the order-1 normal
    loss and its gradient over the parameters."""
    import jax
    import jax.numpy as jnp
    params = jax.eval_shape(lambda k: ref.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    D, C = cfg["in_features"], cfg["out_features"]
    x = jax.ShapeDtypeStruct((ROWS, D), jnp.float32)
    orders = (1, 2, 3) if D == 2 else (1,)
    out = {}
    for n in orders:
        out[f"tower_o{n}"] = count(
            lambda p, x, n=n: ref.tower(cfg, p, x, n), params, x) / ROWS
    t = jax.ShapeDtypeStruct((ROWS, C * D), jnp.float32)
    out["grad_mse_vg_o1"] = count(
        jax.value_and_grad(lambda p, x, t: ref.grad_mse(cfg, p, x, t)),
        params, x, t) / ROWS
    return out


def main(argv) -> int:
    sys.path.insert(0, str(BENCH))
    from harness import load_json, load_module
    for path in argv:
        cfg = load_json(Path(path))
        ref = load_module(BENCH / "reference" / f"{cfg['reference']}.py",
                          "bench_reference")
        print(path, json.dumps(flops_per_row(cfg, ref)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
