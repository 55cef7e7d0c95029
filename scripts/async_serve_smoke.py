"""Async serving smoke gate (wired into scripts/ci.sh; `make async-smoke`).

Fast end-to-end check of the AsyncServingEngine (DESIGN.md §8): compile a
small fleet of SIREN gradient artifacts, stream a mixed single/multi-INR
request sequence through submit/drain, and assert

  * results are BIT-IDENTICAL to one synchronous ``serve`` call over the
    same requests, in request order;
  * chunks actually coalesced (fewer dispatches than requests) and the
    in-flight queue stayed within its double-buffer bound;
  * a second submit/drain round on the same engine stays exact (the
    admission loop resets cleanly between drains).

  PYTHONPATH=src python scripts/async_serve_smoke.py
"""

from __future__ import annotations

import sys
import tempfile

import numpy as np


def main() -> int:
    import jax
    import jax.numpy as jnp

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs.siren import SirenConfig
    from repro.core import pipeline as P
    from repro.core.config import DEFAULT_CONFIG
    from repro.inr.siren import siren_fn, siren_init
    from repro.serve import AsyncServingEngine, ServingEngine

    cfg = SirenConfig(hidden_features=16, hidden_layers=1)
    x = jax.random.uniform(jax.random.PRNGKey(1), (16, cfg.in_features),
                           jnp.float32, -1, 1)
    hw = DEFAULT_CONFIG.replace(block=8, chunk_blocks=4)
    cgs = [P.compile_gradient(siren_fn(cfg, siren_init(
        cfg, jax.random.PRNGKey(k))), 1, x, config=hw) for k in range(3)]

    with tempfile.TemporaryDirectory(prefix="inr-async-smoke-") as root:
        sync = ServingEngine(root + "/s")
        asyn = AsyncServingEngine(root + "/a")
        for k, cg in enumerate(cgs):
            sync.register(f"i{k}", cg)
            asyn.register(f"i{k}", cg)

        rng = np.random.default_rng(7)
        for round_ in range(2):
            reqs = [(f"i{int(rng.integers(3))}",
                     jax.random.uniform(jax.random.PRNGKey(50 * round_ + j),
                                        (int(rng.integers(1, 70)),
                                         cfg.in_features), jnp.float32,
                                        -1, 1))
                    for j in range(10)]
            want = sync.serve(reqs)
            got = asyn.serve_async(reqs)
            for w, g in zip(want, got):
                for a, b in zip(w, g):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))

        st = asyn.stats
        dispatches = (st["async_chunks"] + st["async_blocks"]
                      + st["async_multi_chunks"])
        assert dispatches < st["submitted"], (dispatches, st["submitted"])
        assert st["max_inflight"] <= asyn.inflight, st
        assert asyn.pending_rows() == 0
        print(f"async serve smoke OK: {st['submitted']} requests over "
              f"2 rounds -> {dispatches} dispatches "
              f"({st['async_chunks']} chunks, {st['async_multi_chunks']} "
              f"multi-chunks, {st['async_blocks']} blocks), bit-identical "
              f"to sync, peak inflight {st['max_inflight']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
