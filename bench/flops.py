"""Operations per row of the plain reference, as XLA's cost analysis
counts them: the work a share of the chip's peak is read against.

    python3 bench/flops.py bench/configs/siren-image.json   # print the counts

The counts are recorded as data in each configuration file
(``flops_per_row``), one per ``work`` key its cells' traffic names, so
nothing the program does can move the work a share is read against; a
test recomputes them.  Counted on the CPU backend over ``ROWS`` rows,
divided by ``ROWS``.
"""

from __future__ import annotations

import json
import re
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


TOWER = re.compile(r"tower_o(\d+)")
LOSS = re.compile(r"(\w+)_vg_o(\d+)")


def flops_per_row(cfg: dict, ref) -> dict:
    """Every key of the configuration's ``flops_per_row``, counted anew:
    ``tower_o<n>``, the reference's order-n tower (``ref.tower``);
    ``<loss>_vg_o<n>``, the value and parameter gradient of the
    reference's order-n loss ``ref.<loss>`` against a target of the
    order-n derivative's ``C * D**n`` columns.  Any other key is an
    error."""
    import jax
    import jax.numpy as jnp
    params = jax.eval_shape(lambda k: ref.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    D, C = cfg["in_features"], cfg["out_features"]
    x = jax.ShapeDtypeStruct((ROWS, D), jnp.float32)
    out = {}
    for key in cfg["flops_per_row"]:
        if m := TOWER.fullmatch(key):
            n = int(m[1])
            flops = count(lambda p, x: ref.tower(cfg, p, x, n), params, x)
        elif m := LOSS.fullmatch(key):
            loss, n = getattr(ref, m[1]), int(m[2])
            t = jax.ShapeDtypeStruct((ROWS, C * D ** n), jnp.float32)
            flops = count(jax.value_and_grad(
                lambda p, x, t: loss(cfg, p, x, t)), params, x, t)
        else:
            raise ValueError(f"unknown flops_per_row key {key!r}")
        out[key] = flops / ROWS
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
