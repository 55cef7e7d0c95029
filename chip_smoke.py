#!/usr/bin/env python3
"""Run the INR-Arch gradient compiler's main path once on a TPU and check it.

    python chip_smoke.py [--seed N]          # one chip: every phase below
    python chip_smoke.py --chips 4           # four chips: the fleet phase only

One chip, at the seed SIREN's full width (2 -> 256 x 3 -> 1, w0 = 30), with
random weights made from ``--seed``, in one process:

  serve    orders 1, 2, 3: ``compile_gradient`` into an ``ArtifactStore``,
           ``ServingEngine.serve`` of a full 256 x 256 grid plus two ragged
           requests (1,000 and 4,097 rows), and the same requests through
           ``AsyncServingEngine.submit`` / ``drain``;
  restore  the order-2 artifact restored from the store after
           ``clear_compile_cache()``, one request served, zero tracer calls;
  bank     the five curated filters of ``inr/filters.py`` as one order-2
           bank (``register_bank``) over the grid;
  fit      ``compile_fit`` (``ValueMSE``, order 1), a few AdamW steps of
           ``fit`` over the whole grid; the loss must descend, and the
           streamed ``value_and_grad`` is compared with whole-grid
           ``jax.grad``;
  auto     one order-1 ``compile_gradient(config="auto")``: on a TPU its
           search re-ranks candidates by measured serving time.

Four chips: a fleet of K = 8 seed-width SIRENs at order 2, 4,096 rows each,
served by a ``MultiINRArtifact`` whose stacked weights are sharded along K
over a ``("data",)`` mesh, against the same fleet on one device.

Every output is compared with a float32 ``jax.grad`` tower
(``gradnet.paper_gradients`` over ``siren.siren_fn``) run under
``jax.default_matmul_precision("highest")``, as the scaled error
``max|out - ref| / max|ref|`` per output, against the tolerance written
below for its phase and order.  Each phase also prints its dispatch counts
(fused regions, standalone kernels, interpreted segments), its set-up time
(compile or restore) and its wall time ending in ``block_until_ready``,
and whether its first compile hit JAX's persistent cache.  A phase that
raises stops the run; a check that fails is reported and the run exits 1
after the last phase.  The last line of stdout, on success only, is one
JSON object naming the device.  Without a TPU the script exits non-zero
before any phase runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

GRID_SIDE = 256
RAGGED = (1000, 4097)
TRACE_ROWS = 64            # the paper's batch: example coords for compiling
FIT_STEPS = 5
FLEET_K = 8
FLEET_ROWS = 4096
FILTERS = ("identity", "blur", "edge", "laplacian", "sharpen")

# (phase, order) -> largest scaled error admitted, max|out - ref| / max|ref|
TOL = {
    ("serve", 1): 1e-4, ("serve", 2): 1e-4, ("serve", 3): 1e-4,
    ("restore", 2): 1e-4,
    ("bank", 2): 1e-4,
    ("fit", 1): 1e-4,      # streamed vs whole-grid loss gradient
    ("auto", 1): 1e-4,
    ("fleet", 2): 1e-4,    # sharded vs one device, and vs the reference
}


class Checks:
    """Scaled-error and invariant checks; failures are collected so every
    phase still reports before the run exits non-zero."""

    def __init__(self):
        self.failures: list[str] = []

    def error(self, phase: str, order: int, outs, refs) -> float:
        import numpy as np
        errs = []
        for o, r in zip(outs, refs, strict=True):
            o, r = np.asarray(o, np.float64), np.asarray(r, np.float64)
            if o.shape != r.shape:
                self.failures.append(f"{phase} o{order}: shape {o.shape} "
                                     f"!= reference {r.shape}")
                return float("inf")
            scale = max(float(np.max(np.abs(r))), 1e-30)
            errs.append(float(np.max(np.abs(o - r))) / scale)
        worst = max(errs)
        tol = TOL[(phase, order)]
        ok = np.isfinite(worst) and worst <= tol
        print(f"  {phase} order {order}: scaled error max {worst:.3e} "
              f"(tol {tol:.0e}) per output "
              f"[{', '.join(f'{e:.2e}' for e in errs)}]"
              f"{'' if ok else '  FAIL'}")
        if not ok:
            self.failures.append(f"{phase} o{order}: scaled error "
                                 f"{worst:.3e} > {tol:.0e}")
        return worst

    def require(self, ok: bool, what: str) -> None:
        if not ok:
            print(f"  FAIL: {what}")
            self.failures.append(what)


class CacheEvents:
    """Counts JAX persistent-cache lookups and hits (``jax.monitoring``
    events) so each phase can say whether its first compile was a hit."""

    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.events: list[str] = []
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event in (self.REQUEST, self.HIT):
            self.events.append(event)

    def mark(self) -> int:
        return len(self.events)

    def report(self, since: int) -> str:
        ev = self.events[since:]
        n = ev.count(self.REQUEST)
        hits = ev.count(self.HIT)
        if not n:
            return "cache: no compiles"
        first = ev.index(self.REQUEST)
        first_hit = first + 1 < len(ev) and ev[first + 1] == self.HIT
        return (f"cache: {n} compiles, {hits} hits, first compile "
                f"{'hit' if first_hit else 'miss'}")


def dispatch_counts(cg) -> dict:
    """Execution units of one block step, by how they run."""
    from repro.core.segment import INTERPRET
    counts = {"region": 0, "kernel": 0, "interpreted": 0}
    for _, _, kernel in cg.dispatch:
        if kernel == INTERPRET:
            counts["interpreted"] += 1
        elif kernel.startswith("region"):
            counts["region"] += 1
        else:
            counts["kernel"] += 1
    return counts


def check_artifact(checks: Checks, name: str, cg, *,
                   interpreted_ok: bool = False) -> None:
    """Pallas dispatch must be on; a gradient pipeline must run no
    segment through the per-node interpreter.  (A filter bank's head
    slices and square roots have no kernel step yet: they run as plain XLA
    ops and are only counted.)"""
    counts = dispatch_counts(cg)
    print(f"  {name}: dispatch per block step {counts}")
    checks.require(cg.config.use_pallas, f"{name}: use_pallas is off")
    checks.require(interpreted_ok or counts["interpreted"] == 0,
                   f"{name}: {counts['interpreted']} interpreted segments")


def grid_coords(side: int):
    import jax.numpy as jnp
    lin = jnp.linspace(-1.0, 1.0, side, dtype=jnp.float32)
    xx, yy = jnp.meshgrid(lin, lin, indexing="ij")
    return jnp.stack([xx, yy], -1).reshape(-1, 2)


def reference(f, order: int, coords):
    """The plain float32 jax.grad tower at full matmul precision."""
    import jax
    from repro.inr.gradnet import paper_gradients
    with jax.default_matmul_precision("highest"):
        return jax.block_until_ready(
            jax.jit(paper_gradients(f, order, 1, int(coords.shape[-1])))(
                coords))


def timed(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def run_single_chip(*, seed: int, siren=None, grid_side: int = GRID_SIDE,
                    ragged=RAGGED, fit_steps: int = FIT_STEPS,
                    hw=None, cache: CacheEvents | None = None) -> list[str]:
    """Every one-chip phase; returns the failed checks.  ``siren`` and the
    sizes default to the seed configuration; ``hw`` (a HardwareConfig or
    None for the defaults) is what every compile is given."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.siren import SirenConfig
    from repro.core import pipeline as P
    from repro.core import trace
    from repro.fit import ValueMSE, compile_fit, fit
    from repro.inr.filters import filter_bank, filter_head
    from repro.inr.siren import siren_apply, siren_fn, siren_init
    from repro.serve import ArtifactStore, AsyncServingEngine, ServingEngine

    checks = Checks()
    cfg = siren if siren is not None else SirenConfig()
    k_w, k_q = jax.random.split(jax.random.PRNGKey(seed))
    params = siren_init(cfg, k_w)
    f = siren_fn(cfg, params)
    grid = grid_coords(grid_side)
    queries = [grid] + [
        jax.random.uniform(k, (n, cfg.in_features), jnp.float32, -1, 1)
        for k, n in zip(jax.random.split(k_q, len(ragged)), ragged)]
    x_trace = grid[:TRACE_ROWS]
    print(f"SIREN {cfg.in_features} -> {cfg.hidden_features} x "
          f"{cfg.hidden_layers} -> {cfg.out_features}, w0={cfg.w0}, "
          f"seed {seed}; requests {[int(q.shape[0]) for q in queries]} rows")

    def phase(name):
        print(f"[{name}]")
        return cache.mark() if cache is not None else 0

    def done(mark):
        if cache is not None:
            print(f"  {cache.report(mark)}")

    refs = {}
    with tempfile.TemporaryDirectory(prefix="inr-chip-smoke-") as tmp:
        store = ArtifactStore(os.path.join(tmp, "store"))
        engine = ServingEngine(store)
        async_engine = AsyncServingEngine(store)
        compiled = {}

        # -- serve: orders 1-3, sync and async ------------------------------
        for order in (1, 2, 3):
            mark = phase(f"serve order {order}")
            t0 = time.perf_counter()
            cg = P.compile_gradient(f, order, x_trace, config=hw,
                                    store=store)
            inr = f"siren-o{order}"
            engine.register(inr, cg)
            async_engine.register(inr, cg)
            setup = time.perf_counter() - t0
            compiled[order] = cg
            check_artifact(checks, f"order {order}", cg)
            refs[order] = [reference(f, order, q) for q in queries]
            reqs = [(inr, q) for q in queries]
            outs, first = timed(engine.serve, reqs)
            outs, warm = timed(engine.serve, reqs)
            flat_out = [o for req in outs for o in req]
            flat_ref = [r for req in refs[order] for r in req]
            checks.error("serve", order, flat_out, flat_ref)

            def submit_drain():
                for q in queries:
                    async_engine.submit(inr, q)
                return async_engine.drain()
            aouts, async_s = timed(submit_drain)
            checks.error("serve", order, [o for req in aouts for o in req],
                         flat_ref)
            rows = sum(int(q.shape[0]) for q in queries)
            print(f"  setup {setup:.3f}s (trace+compile+store), "
                  f"first serve {first:.3f}s, warm serve {warm:.3f}s, "
                  f"async submit/drain {async_s:.3f}s, {rows} rows")
            done(mark)

        # -- restore: order 2 from the store, no tracer ---------------------
        mark = phase("restore order 2")
        sig2 = compiled[2].signature
        P.clear_compile_cache()
        traces = trace.TRACE_CALLS
        replica = ServingEngine(store)
        replica.register("restored-o2", signature=sig2, weight_id="siren-o2")
        (outs,), setup = timed(replica.serve,
                               [("restored-o2", queries[-1])])
        restored = replica._artifact(sig2)
        checks.require(restored.provenance == "store",
                       f"restore: provenance {restored.provenance!r}")
        checks.require(trace.TRACE_CALLS == traces,
                       f"restore: {trace.TRACE_CALLS - traces} tracer calls")
        check_artifact(checks, "restored order 2", restored)
        checks.error("restore", 2, outs, refs[2][-1])
        print(f"  restore + first serve {setup:.3f}s, tracer calls "
              f"{trace.TRACE_CALLS - traces}")
        done(mark)

        # -- bank: five filters, one order-2 pass ---------------------------
        mark = phase("bank order 2")
        t0 = time.perf_counter()
        bank = filter_bank(f, FILTERS, x_trace, config=hw, store=store)
        engine.register_bank(FILTERS, bank)
        setup = time.perf_counter() - t0
        check_artifact(checks, "bank", bank.cg,
                       interpreted_ok=True)
        feats = jnp.concatenate(
            [o.reshape(grid.shape[0], -1) for o in refs[2][0]], -1)
        with jax.default_matmul_precision("highest"):
            want = [filter_head(n, cfg.in_features, cfg.out_features)(feats)
                    for n in FILTERS]
        reqs = [(n, grid) for n in FILTERS]
        outs, first = timed(engine.serve, reqs)
        outs, warm = timed(engine.serve, reqs)
        checks.error("bank", 2, [o for (o,) in outs], want)
        print(f"  setup {setup:.3f}s, first serve {first:.3f}s, "
              f"warm serve {warm:.3f}s, {len(FILTERS)} filters x "
              f"{grid.shape[0]} rows")
        done(mark)

        # -- fit: order 1, ValueMSE, whole grid -----------------------------
        mark = phase("fit order 1")
        u, v = grid[:, 0], grid[:, 1]
        target = (jnp.sin(3 * jnp.pi * u) * jnp.cos(2 * jnp.pi * v))[:, None]
        t0 = time.perf_counter()
        cf = compile_fit(f, ValueMSE(), 1, x_trace, params=params,
                         config=hw, store=store)
        setup = time.perf_counter() - t0
        check_artifact(checks, "fit", cf.cg)

        def whole(p):
            y = siren_apply(p, grid, cfg.w0)
            return jnp.mean(jnp.sum((y - target) ** 2, -1))
        with jax.default_matmul_precision("highest"):
            l_ref, g_ref = jax.jit(jax.value_and_grad(whole))(params)
        (l_st, g_st), vg_s = timed(jax.jit(cf.value_and_grad), params, grid,
                                   target)
        leaves_st = jax.tree_util.tree_leaves(g_st)
        leaves_ref = jax.tree_util.tree_leaves(g_ref)
        checks.error("fit", 1, [l_st] + leaves_st, [l_ref] + leaves_ref)
        res = fit(cf, grid, target, steps=fit_steps)
        losses = res.losses
        checks.require(bool(np.all(np.isfinite(losses)))
                       and losses[-1] < losses[0],
                       f"fit: loss did not descend {losses}")
        print(f"  setup {setup:.3f}s, streamed value_and_grad "
              f"{vg_s:.3f}s (first call), {fit_steps} steps "
              f"{res.wall_s:.3f}s, losses "
              f"{', '.join(f'{l:.6f}' for l in losses)}")
        done(mark)

        # -- auto: the measured re-rank ------------------------------------
        mark = phase("auto order 1")
        t0 = time.perf_counter()
        cga = P.compile_gradient(f, 1, x_trace, config="auto",
                                 base_config=hw)
        setup = time.perf_counter() - t0
        check_artifact(checks, "auto", cga)
        outs, first = timed(cga.apply_batched, grid)
        checks.error("auto", 1, outs, refs[1][0])
        print(f"  setup {setup:.3f}s (search, measured on "
              f"{jax.default_backend()}), first serve {first:.3f}s, "
              f"{cga.autoconfig.evaluated} candidates scored; chose "
              f"[{cga.config.describe()}]")
        done(mark)
    return checks.failures


def run_fleet(*, seed: int, siren=None, k: int = FLEET_K,
              rows: int = FLEET_ROWS, n_devices: int = 4,
              hw=None) -> list[str]:
    """K INRs at order 2: K-sharded over ``n_devices`` vs one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.siren import SirenConfig
    from repro.core import pipeline as P
    from repro.distributed.sharding import ShardingPolicy, make_mesh
    from repro.inr.siren import siren_fn, siren_init
    from repro.serve import MultiINRArtifact, bind_weights

    checks = Checks()
    cfg = siren if siren is not None else SirenConfig()
    keys = jax.random.split(jax.random.PRNGKey(seed), k + 1)
    fleet = [siren_init(cfg, kk) for kk in keys[:k]]
    coords = jax.random.uniform(keys[k], (k, rows, cfg.in_features),
                                jnp.float32, -1, 1)
    print(f"[fleet order 2] K={k} SIRENs {cfg.in_features} -> "
          f"{cfg.hidden_features} x {cfg.hidden_layers} -> "
          f"{cfg.out_features}, {rows} rows each, {n_devices} devices")
    t0 = time.perf_counter()
    base = P.compile_gradient(siren_fn(cfg, fleet[0]), 2,
                              coords[0, :TRACE_ROWS], config=hw)
    payloads = [bind_weights(base, fleet[0], p) for p in fleet]
    ids = [f"inr{i}" for i in range(k)]
    mesh = make_mesh((n_devices,), ("data",),
                     devices=jax.devices()[:n_devices])
    sharded = MultiINRArtifact(base, payloads, ids,
                               sharding=ShardingPolicy(mesh))
    single = MultiINRArtifact(base, payloads, ids)
    setup = time.perf_counter() - t0
    check_artifact(checks, "fleet base", base)
    checks.require(sharded.k_sharded, "fleet: K axis not sharded")

    out_sh, sh_s = timed(sharded.apply_batched, coords)
    out_1, one_s = timed(single.apply_batched, coords)
    checks.error("fleet", 2, out_sh, out_1)
    for i in (0, k - 1):
        want = reference(siren_fn(cfg, fleet[i]), 2, coords[i])
        checks.error("fleet", 2, [o[i] for o in out_sh], want)

    per_device: dict = {}
    for v in sharded.residents.values():
        for s in v.addressable_shards:
            per_device[s.device.id] = (per_device.get(s.device.id, 0)
                                       + s.data.nbytes)
    total = sum(int(v.nbytes) for v in sharded.residents.values())
    block = base.config.block
    xb = jnp.moveaxis(coords.reshape(k, rows // block, block, -1), 0, 1)
    hlo = sharded._serve_fn.lower(sharded.residents,
                                  sharded.place_batch(xb)).compile().as_text()
    gathers = hlo.count("all-gather")
    checks.require(gathers == 0, f"fleet: {gathers} all-gathers in the "
                   f"sharded program (K gathered, not split)")
    print(f"  stacked residents {total} bytes; per device "
          f"{dict(sorted(per_device.items()))}")
    print(f"  sharded program: {gathers} all-gathers, "
          f"{hlo.count('tpu_custom_call')} tpu_custom_calls")
    print(f"  setup {setup:.3f}s, sharded first serve {sh_s:.3f}s, "
          f"one-device first serve {one_s:.3f}s")
    return checks.failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the K-sharded fleet phase")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        sys.exit(f"chip_smoke: no src/repro next to {__file__}; run it "
                 f"from a checkout of the repository")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{jax.default_backend()!r}; nothing was run")
    from repro.compile_cache import enable_compile_cache
    from repro.kernels.common import interpret_default
    if interpret_default():
        sys.exit("chip_smoke: Pallas would run in interpret mode")
    devices = jax.devices()
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, found {len(devices)}")
    cache_dir = enable_compile_cache()
    cache = CacheEvents()
    print(f"jax {jax.__version__} on {len(devices)} x "
          f"{devices[0].device_kind}; compile cache {cache_dir}")

    t0 = time.perf_counter()
    if args.chips == 4:
        mark = cache.mark()
        failures = run_fleet(seed=args.seed, n_devices=4)
        print(f"  {cache.report(mark)}")
    else:
        failures = run_single_chip(seed=args.seed, cache=cache)
    print(f"total {time.perf_counter() - t0:.3f}s")
    if failures:
        print(f"chip_smoke: {len(failures)} check(s) failed:")
        for f_ in failures:
            print(f"  {f_}")
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
