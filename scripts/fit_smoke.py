"""Fit-pipeline smoke gate (wired into scripts/ci.sh; `make fit-smoke`).

One tiny end-to-end pass of the streamed fitting engine (DESIGN.md §11):
compile a first-order fit artifact for a small SIREN, run a handful of
AdamW steps against a synthetic target, stream the converged weights into
an ArtifactStore, and serve them back through a ServingEngine — asserting

  * the per-step loss sequence DESCENDS (the optimizer is really wired to
    the streamed gradient);
  * the streamed gradient matches a whole-grid ``jax.grad`` reference
    (scaled error <= 1e-5) on a non-block-multiple grid;
  * the served value channel of the fitted weights matches a direct
    ``siren_apply`` of the fitted params (fit -> put_weights -> serve
    round-trips without a re-trace);
  * the ``fit_steps`` / ``fit_weight_puts`` metrics and the
    ``fit_peak_bytes`` gauge moved.

  PYTHONPATH=src python scripts/fit_smoke.py
"""

from __future__ import annotations

import sys
import tempfile


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs.siren import SirenConfig
    from repro.core.config import HardwareConfig
    from repro.fit import GradMSE, ValueMSE, compile_fit, fit
    from repro.inr.gradnet import batched_gradients
    from repro.inr.siren import siren_apply, siren_fn, siren_init
    from repro.obs import metrics
    from repro.serve import ArtifactStore, ServingEngine

    scfg = SirenConfig(hidden_features=16, hidden_layers=1)
    params = siren_init(scfg, jax.random.PRNGKey(0))
    f = siren_fn(scfg, params)
    hw = HardwareConfig(block=8)
    ex = jax.random.uniform(jax.random.PRNGKey(1), (16, 2), jnp.float32,
                            -1, 1)
    coords = jax.random.uniform(jax.random.PRNGKey(2), (45, 2), jnp.float32,
                                -1, 1)                  # not a block multiple

    # streamed gradient vs whole-grid jax.grad, order 1
    gloss = GradMSE()
    gt = jax.random.normal(jax.random.PRNGKey(3), (45, 2), jnp.float32)
    cfg1 = compile_fit(f, gloss, 1, ex, params=params, config=hw)

    def whole(p):
        y, dy = batched_gradients(siren_fn(scfg, p), 1)(coords)
        return jnp.mean(gloss.row_loss((y, dy[:, 0]), gt, 1, 2))

    l_ref, g_ref = jax.value_and_grad(whole)(params)
    l_st, g_st = cfg1.value_and_grad(params, coords, gt)
    err = max(
        float(jnp.max(jnp.abs(a - b)))
        / max(1.0, float(jnp.max(jnp.abs(b))))
        for a, b in zip(jax.tree_util.tree_leaves(g_st),
                        jax.tree_util.tree_leaves(g_ref)))
    assert err <= 1e-5, f"streamed-vs-whole-grid gradient error {err:.2e}"
    assert abs(float(l_st) - float(l_ref)) <= 1e-5
    print(f"fit_smoke: streamed gradient parity {err:.2e} <= 1e-5")

    # fit -> store -> serve round-trip
    with tempfile.TemporaryDirectory() as d:
        store = ArtifactStore(d)
        target = jnp.tanh(2.0 * coords[:, :1])
        cf = compile_fit(f, ValueMSE(), 1, ex, params=params, config=hw,
                         store=store)
        r = fit(cf, coords, target, steps=6, store=store, inr_id="fitted")
        assert r.losses[-1] < r.losses[0], r.losses
        print(f"fit_smoke: loss {r.losses[0]:.5f} -> {r.losses[-1]:.5f} "
              f"over {r.steps} steps")

        eng = ServingEngine(store)
        eng.register("fitted", signature=cf.signature, weight_id="fitted")
        (outs,) = eng.serve([("fitted", coords)])
        ref = siren_apply(r.params, coords)
        d_max = float(jnp.max(jnp.abs(outs[0] - ref)))
        assert d_max <= 1e-5, f"served-vs-fitted mismatch {d_max:.2e}"
        print(f"fit_smoke: fit -> put_weights -> serve parity "
              f"{d_max:.2e} <= 1e-5")

    steps_v = metrics.counter("fit_steps", "").value()
    puts_v = metrics.counter("fit_weight_puts", "").value()
    peak_v = metrics.gauge("fit_peak_bytes", "").value()
    assert steps_v >= 6, steps_v
    assert puts_v >= 1, puts_v
    assert peak_v > 0, peak_v
    print(f"fit_smoke: metrics fit_steps={steps_v:.0f} "
          f"fit_weight_puts={puts_v:.0f} fit_peak_bytes={peak_v:.0f}")
    print("fit_smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
