"""Store round-trip smoke gate (wired into scripts/ci.sh; `make serve-smoke`).

The parent process never imports JAX: it runs two children, one after the
other, so that only one process at a time holds the device.

Write child (--write DIR): compile a small 2nd-order SIREN gradient
pipeline, persist it to an ArtifactStore under DIR, and save the weights,
query coords and expected outputs.

Restore child (--restore DIR): in a fresh interpreter, poison the tracer,
rebuild the INR fn from the saved weights, and go through BOTH restore
paths — ``store.load(signature)`` and the ``compile_gradient(...,
store=...)`` disk-index hit — asserting zero tracer invocations and exact
numeric parity with the expected outputs from the write child.

  PYTHONPATH=src python scripts/serve_smoke.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

import numpy as np


def _setup():
    import jax
    import jax.numpy as jnp

    from repro.configs.siren import SirenConfig
    from repro.inr.siren import siren_fn, siren_init

    cfg = SirenConfig(hidden_features=16, hidden_layers=1)
    params = siren_init(cfg, jax.random.PRNGKey(0))
    f = siren_fn(cfg, params)
    x = jax.random.uniform(jax.random.PRNGKey(1), (16, cfg.in_features),
                           jnp.float32, -1, 1)
    q = jax.random.uniform(jax.random.PRNGKey(2), (13, cfg.in_features),
                           jnp.float32, -1, 1)
    return cfg, params, f, x, q


def write_phase(workdir: str) -> int:
    from repro.checkpoint import ckpt
    from repro.core import pipeline as P
    from repro.serve.store import ArtifactStore

    cfg, params, f, x, q = _setup()
    store = ArtifactStore(os.path.join(workdir, "store"))
    cg = P.compile_gradient(f, 2, x, store=store)
    want = cg.apply_batched(q)

    ckpt.save(params, os.path.join(workdir, "weights"))
    np.savez(os.path.join(workdir, "io.npz"), x=np.asarray(x),
             q=np.asarray(q), **{f"out{i}": np.asarray(o)
                                 for i, o in enumerate(want)})
    with open(os.path.join(workdir, "meta.json"), "w") as f_:
        json.dump({"signature": cg.signature, "n_outputs": len(want),
                   "weight_sets": store.info()["weight_sets"]}, f_)
    print(f"  [write] persisted {cg.signature}")
    return 0


def _child(phase: str, workdir: str) -> bool:
    env = dict(os.environ)
    env["PYTHONPATH"] = _src_path() + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    r = subprocess.run([sys.executable, os.path.abspath(__file__),
                        phase, workdir],
                       env=env, capture_output=True, text=True, timeout=420)
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr)
    if r.returncode != 0:
        print(f"serve smoke FAILED in the {phase} subprocess")
    return r.returncode == 0


def _src_path() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")


def restore_phase(workdir: str) -> int:
    import repro.core.trace as T

    def _no_trace(*a, **kw):
        raise AssertionError("tracer invoked during warm-store restore")

    real_extract = T.extract_graph
    T.extract_graph = _no_trace          # poison: restore must never trace

    from repro.checkpoint import ckpt
    from repro.core import pipeline as P
    from repro.inr.siren import siren_fn, siren_init
    from repro.serve.store import ArtifactStore

    import jax

    from repro.configs.siren import SirenConfig
    cfg = SirenConfig(hidden_features=16, hidden_layers=1)
    template = siren_init(cfg, jax.random.PRNGKey(0))
    params, _ = ckpt.restore(template, os.path.join(workdir, "weights"))
    f = siren_fn(cfg, params)

    with open(os.path.join(workdir, "meta.json")) as f_:
        meta = json.load(f_)
    io = np.load(os.path.join(workdir, "io.npz"))
    x, q = io["x"], io["q"]
    want = [io[f"out{i}"] for i in range(meta["n_outputs"])]

    store = ArtifactStore(os.path.join(workdir, "store"))

    # path 1: restore by signature (what a serving replica does)
    cg = store.load(meta["signature"])
    assert cg.provenance == "store", cg.provenance
    for a, b in zip(want, cg.apply_batched(q)):
        np.testing.assert_array_equal(a, np.asarray(b))

    # path 2: the compile_gradient three-level lookup hits the disk index
    cg2 = P.compile_gradient(f, 2, x, store=store)
    assert cg2.provenance == "store", cg2.provenance
    info = P.compile_cache_info()
    assert info["store_hits"] == 1, info
    for a, b in zip(want, cg2.apply_batched(q)):
        np.testing.assert_array_equal(a, np.asarray(b))

    assert T.TRACE_CALLS == 0, f"tracer ran {T.TRACE_CALLS} times"
    T.extract_graph = real_extract
    print(f"  [subprocess] restored {meta['signature']} twice "
          f"(load + index hit), 0 traces, exact parity on {q.shape[0]} rows")
    return 0


def main() -> int:
    if len(sys.argv) >= 3 and sys.argv[1] in ("--write", "--restore"):
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
        phase = write_phase if sys.argv[1] == "--write" else restore_phase
        return phase(sys.argv[2])
    with tempfile.TemporaryDirectory(prefix="inr-serve-smoke-") as workdir:
        if not (_child("--write", workdir) and _child("--restore", workdir)):
            return 1
        with open(os.path.join(workdir, "meta.json")) as f_:
            meta = json.load(f_)
    print(f"serve smoke OK: signature {meta['signature']}, "
          f"{meta['weight_sets']} weight set(s), subprocess restored "
          f"with zero tracer invocations and exact parity")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
