"""Calibrate the dataflow oracle's per-op row costs against the hardware.

``core.dataflow.OP_ROW_COST`` is ANALYTIC: elementwise ops cost 1 row-cycle,
transcendentals 2, an MM ``ceil(K / parallelism)``.  This script replaces
the analytics with MEASURED ratios on whatever backend jax resolves (the TPU
the kernels target, or the CPU interpret path in dev):

  * every elementwise / transcendental op is timed on a ``[rows, cols]``
    f32 block (jitted, ``block_until_ready``); its row cost is its per-row
    time relative to an ``Add`` on the same block (the II=1 unit), rounded
    to an int >= 1 — the same normalization the analytic table uses;
  * the MM is timed as ``[rows, K] @ [K, N]``; its calibration is the
    continuous scale ``mm_row_cost_per_k`` (measured per-row-per-K time
    over the Add unit), which ``dataflow.segment_row_cost`` multiplies into
    ``ceil(K * scale / parallelism)``;
  * the host -> shard interconnect hop is timed as a ``device_put`` of a
    host-resident block; its per-row time over the Add unit becomes
    ``xshard_row_cost``, which ``dataflow.map_to_dataflow`` charges on the
    xshard forwarder edge of every pipeline input under a sharded mesh
    (``config.n_shards > 1``) in place of the static
    ``config.xshard_row_cost`` default.

Output is JSON under ``results/`` (default ``results/op_row_cost.json``),
loadable with ``dataflow.load_op_row_cost()`` — explicit opt-in, never
auto-loaded, so analyses stay deterministic by default.

  PYTHONPATH=src python scripts/row_cost_calibrate.py [--rows N] [--out F]
"""

from __future__ import annotations

import argparse
import json
import time

# the ops the oracle distinguishes; each is a jnp expression of one block
_UNARY = {
    "Add": lambda jnp: (lambda x: x + x),
    "Mul": lambda jnp: (lambda x: x * x),
    "Sin": lambda jnp: (lambda x: jnp.sin(x)),
    "Cos": lambda jnp: (lambda x: jnp.cos(x)),
    "Exp": lambda jnp: (lambda x: jnp.exp(x)),
    "Log": lambda jnp: (lambda x: jnp.log(jnp.abs(x) + 1.0)),
    "Tanh": lambda jnp: (lambda x: jnp.tanh(x)),
    "Sigmoid": lambda jnp: (lambda x: 1.0 / (1.0 + jnp.exp(-x))),
    "Erf": lambda jnp: (lambda x: __import__("jax").lax.erf(x)),
    "Rsqrt": lambda jnp: (lambda x: __import__("jax").lax.rsqrt(
        jnp.abs(x) + 1.0)),
    "Sqrt": lambda jnp: (lambda x: jnp.sqrt(jnp.abs(x))),
    "Pow": lambda jnp: (lambda x: x ** 2.5),
    "IntPow": lambda jnp: (lambda x: __import__("jax").lax.integer_pow(x, 3)),
}


def _median_time(fn, arg, *, warmup: int, iters: int) -> float:
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(arg))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(arg))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def calibrate(rows: int = 4096, cols: int = 256, k: int = 256,
              warmup: int = 2, iters: int = 7) -> dict:
    import jax
    import jax.numpy as jnp

    x = jax.random.uniform(jax.random.PRNGKey(0), (rows, cols), jnp.float32,
                           -1.0, 1.0)
    per_op_s: dict[str, float] = {}
    for name, make in _UNARY.items():
        fn = jax.jit(make(jnp))
        per_op_s[name] = _median_time(fn, x, warmup=warmup, iters=iters)

    unit = per_op_s["Add"] / rows          # seconds per row of the II=1 op
    table = {name: max(1, round((t / rows) / unit))
             for name, t in per_op_s.items() if name != "Add"}

    # MM: per-row-per-K time over the Add unit
    xa = jax.random.uniform(jax.random.PRNGKey(1), (rows, k), jnp.float32)
    w = jax.random.uniform(jax.random.PRNGKey(2), (k, cols), jnp.float32)
    mm = jax.jit(lambda a: a @ w)
    mm_s = _median_time(mm, xa, warmup=warmup, iters=iters)
    mm_row_cost_per_k = max(1e-6, (mm_s / rows / k) / unit)

    # host -> device hop: a device_put of a host-resident block (the
    # interconnect transfer a sharded mesh pays per input block)
    import numpy as np
    host_block = np.asarray(x)
    put = lambda a: jax.device_put(a)
    xshard_s = _median_time(put, host_block, warmup=warmup, iters=iters)
    xshard_row_cost = max(1, round((xshard_s / rows) / unit))

    return {
        "meta": {"backend": jax.default_backend(), "rows": rows,
                 "cols": cols, "k": k, "iters": iters,
                 "unit_s_per_row": unit},
        "op_row_cost": table,
        "mm_row_cost_per_k": mm_row_cost_per_k,
        "xshard_row_cost": xshard_row_cost,
    }


def main(argv=None) -> int:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=4096)
    ap.add_argument("--cols", type=int, default=256)
    ap.add_argument("--k", type=int, default=256)
    ap.add_argument("--iters", type=int, default=7)
    ap.add_argument("--out", default="results/op_row_cost.json")
    args = ap.parse_args(argv)

    result = calibrate(rows=args.rows, cols=args.cols, k=args.k,
                       iters=args.iters)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
        f.write("\n")

    # round-trip through the loader so the emitted file is known-good
    from repro.core import dataflow
    loaded = dataflow.load_op_row_cost(args.out)
    dataflow.reset_op_row_cost()
    costs = " ".join(f"{k_}={v}" for k_, v in
                     sorted(result["op_row_cost"].items()))
    print(f"row costs [{result['meta']['backend']}]: {costs} "
          f"mm_per_k={result['mm_row_cost_per_k']:.3g} "
          f"xshard={result['xshard_row_cost']} -> {args.out} "
          f"({len(loaded)} ops active after load)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
