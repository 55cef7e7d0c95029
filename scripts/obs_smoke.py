"""Observability smoke gate (wired into scripts/ci.sh; `make obs-smoke`).

Fast end-to-end check of the telemetry layer (DESIGN.md §10): compile a
small SIREN gradient artifact WITH TRACING ON, drain a mixed request
stream through the async engine, then assert

  * the exported Chrome/Perfetto trace is valid trace-event JSON with
    nested compile-stage spans AND per-chunk serve spans (written to
    ``results/obs_trace.json`` — open it at https://ui.perfetto.dev);
  * the Prometheus text exposition parses (TYPE line per metric, one
    sample line per labeled timeseries; written to ``results/obs.prom``);
  * engine/compile-cache stats read through the metrics registry (one
    source of truth, two views);
  * ``drift_report`` runs on orders 1–3 with every FIFO's runtime
    high-water within its configured depth (non-negative headroom) and a
    JSON-serializable report.

  PYTHONPATH=src python scripts/obs_smoke.py
"""

from __future__ import annotations

import json
import pathlib
import re
import sys
import tempfile

RESULTS = pathlib.Path(__file__).resolve().parent.parent / "results"


def validate_chrome_trace(doc: dict) -> list[str]:
    evs = doc.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        return ["traceEvents missing or empty"]
    errs = []
    for e in evs:
        missing = {"name", "cat", "ph", "ts", "dur", "pid", "tid"} - set(e)
        if missing:
            errs.append(f"event {e.get('name')!r} missing {sorted(missing)}")
        elif e["ph"] != "X" or e["ts"] < 0 or e["dur"] < 0:
            errs.append(f"event {e['name']!r} malformed: ph={e['ph']} "
                        f"ts={e['ts']} dur={e['dur']}")
    return errs


def validate_prometheus(text: str) -> list[str]:
    sample = re.compile(r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? '
                        r'[-+0-9.e]+$')
    typed = set()
    errs = []
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            typed.add(line.split()[2])
        elif not line.startswith("#"):
            if not sample.match(line):
                errs.append(f"malformed sample line: {line!r}")
            else:
                base = line.split("{")[0].split(" ")[0]
                base = re.sub(r"_(bucket|sum|count)$", "", base)
                if base not in typed and line.split()[0] not in typed:
                    errs.append(f"sample {base!r} has no TYPE line")
    if not typed:
        errs.append("no TYPE lines at all")
    return errs


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs.siren import SirenConfig
    from repro.core import pipeline as P
    from repro.core.config import DEFAULT_CONFIG
    from repro.inr.siren import siren_fn, siren_init
    from repro.obs import REGISTRY, TRACER, drift_report
    from repro.obs.drift import fifo_high_water
    from repro.serve import AsyncServingEngine

    cfg = SirenConfig(hidden_features=16, hidden_layers=1)
    x = jax.random.uniform(jax.random.PRNGKey(1), (16, cfg.in_features),
                           jnp.float32, -1, 1)
    hw = DEFAULT_CONFIG.replace(block=8, chunk_blocks=4)

    P.clear_compile_cache()
    TRACER.clear()
    failures: list[str] = []

    with TRACER.enabled_scope(), \
            tempfile.TemporaryDirectory(prefix="inr-obs-smoke-") as root:
        cgs = [P.compile_gradient(siren_fn(cfg, siren_init(
            cfg, jax.random.PRNGKey(k))), 1, x, config=hw) for k in range(3)]
        eng = AsyncServingEngine(root + "/a")
        for k, cg in enumerate(cgs):
            eng.register(f"i{k}", cg)
        rng = np.random.default_rng(7)
        for j in range(9):
            q = jax.random.uniform(jax.random.PRNGKey(50 + j),
                                   (int(rng.integers(3, 40)),
                                    cfg.in_features), jnp.float32, -1, 1)
            eng.submit(f"i{j % 3}", q)
        outs = eng.drain()
        assert len(outs) == 9 and all(o for o in outs)

    # -- trace export -------------------------------------------------------
    RESULTS.mkdir(exist_ok=True)
    trace_path = RESULTS / "obs_trace.json"
    doc = json.loads(TRACER.export_chrome_json(str(trace_path)))
    failures += validate_chrome_trace(doc)
    names = {e["name"] for e in doc["traceEvents"]}
    for want in ("compile", "compile.trace", "compile.segment_plan",
                 "compile.codegen", "serve.retire", "serve.unpad",
                 "serve.dispatch", "serve.pad"):
        if want not in names:
            failures.append(f"span {want!r} missing from trace")
    if not names & {"serve.chunk", "serve.chunk.multi", "serve.block"}:
        failures.append("no per-chunk serve span in trace")
    by_name = {e["name"]: e for e in doc["traceEvents"]}
    top, stage = by_name.get("compile"), by_name.get("compile.trace")
    if top and stage and not (top["ts"] <= stage["ts"] and
                              stage["ts"] + stage["dur"]
                              <= top["ts"] + top["dur"] + 1e-6):
        failures.append("compile.trace not nested inside compile span")
    print(f"[obs-smoke] trace: {len(doc['traceEvents'])} events, "
          f"{len(names)} span kinds -> {trace_path}")
    TRACER.clear()

    # -- metrics exposition + read-through ----------------------------------
    prom_path = RESULTS / "obs.prom"
    text = REGISTRY.prometheus_text()
    prom_path.write_text(text)
    failures += validate_prometheus(text)
    lab = eng.stats.labels["engine"]
    if REGISTRY.get("serve_submitted").value(engine=lab) \
            != eng.stats["submitted"] or eng.stats["submitted"] != 9:
        failures.append(f"engine stats/registry disagree: "
                        f"{eng.stats['submitted']} submitted")
    if REGISTRY.get("compile_cache_misses").value() \
            != P.compile_cache_info()["misses"]:
        failures.append("compile cache stats/registry disagree")
    print(f"[obs-smoke] metrics: {len(REGISTRY.names())} registered "
          f"-> {prom_path}")

    # -- drift report, orders 1..3 ------------------------------------------
    for order in (1, 2, 3):
        cg = P.compile_gradient(siren_fn(cfg, siren_init(
            cfg, jax.random.PRNGKey(order))), order, x, config=hw)
        rep = drift_report(cg, iters=2, warmup=1)
        json.dumps(rep.as_dict())                  # must serialize
        if rep.min_headroom < 0:
            failures.append(f"order {order}: FIFO high-water exceeds "
                            f"configured depth ({rep.min_headroom})")
        df = cg.dataflow_summary()
        high = fifo_high_water(df["design"], df["fifo"].depths_after)
        print(f"[obs-smoke] drift order {order}: {len(rep.units)} units, "
              f"max drift {rep.max_drift:.2f}x, fifo high-water "
              f"{max(high.values())}/{max(df['fifo'].depths_after.values())}")

    if failures:
        for f in failures:
            print(f"[obs-smoke] FAIL: {f}", file=sys.stderr)
        return 1
    print("[obs-smoke] OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
