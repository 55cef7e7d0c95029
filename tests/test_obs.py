"""Unified telemetry layer (DESIGN.md §10): metrics registry + read-through
views, deterministic histogram percentiles, span tracing with Perfetto
export, model-vs-measured drift reports, FIFO high-water headroom, the
structured launch logger, and the ≤5% serve-overhead gate."""

import glob
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.siren import SirenConfig
from repro.core import pipeline as P
from repro.core.config import DEFAULT_CONFIG
from repro.inr.siren import siren_fn, siren_init
from repro.obs import log as obslog
from repro.obs.metrics import (REGISTRY, Counter, Histogram, MetricsRegistry,
                               MetricsView)
from repro.obs.tracing import TRACER, Tracer
from repro.serve import AsyncServingEngine, ServingEngine


@pytest.fixture(autouse=True)
def fresh_cache():
    P.clear_compile_cache()
    TRACER.disable()
    TRACER.clear()
    yield
    P.clear_compile_cache()
    TRACER.disable()
    TRACER.clear()


HW = DEFAULT_CONFIG.replace(block=8, chunk_blocks=4)


@pytest.fixture(scope="module")
def small_inr():
    cfg = SirenConfig(hidden_features=16, hidden_layers=1)
    f = siren_fn(cfg, siren_init(cfg, jax.random.PRNGKey(0)))
    x = jax.random.uniform(jax.random.PRNGKey(1),
                           (16, cfg.in_features), jnp.float32, -1, 1)
    return cfg, f, x


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_idempotent_and_kind_checked():
    r = MetricsRegistry()
    c1 = r.counter("reqs", "requests")
    c2 = r.counter("reqs")
    assert c1 is c2 and isinstance(c1, Counter)
    with pytest.raises(TypeError):
        r.gauge("reqs")
    assert r.names() == ["reqs"]


def test_labels_are_separate_timeseries():
    r = MetricsRegistry()
    c = r.counter("rows")
    c.inc(3, engine="e0")
    c.inc(5, engine="e1")
    c.inc(1)
    assert c.value(engine="e0") == 3
    assert c.value(engine="e1") == 5
    assert c.value() == 1
    snap = r.snapshot()["rows"]
    assert snap["kind"] == "counter"
    assert snap["values"] == {'{engine="e0"}': 3.0, '{engine="e1"}': 5.0,
                              "": 1.0}


def test_reset_keeps_registrations_zeroes_values():
    r = MetricsRegistry()
    c = r.counter("serve_x")
    g = r.gauge("compile_y")
    c.inc(7, engine="e0")
    g.set(4)
    r.reset(prefix="serve_")
    assert c.value(engine="e0") == 0 and g.value() == 4
    r.reset()
    assert g.value() == 0
    assert r.names() == ["compile_y", "serve_x"]


def test_prometheus_text_format():
    r = MetricsRegistry()
    r.counter("reqs", "total requests").inc(2, engine="e0")
    h = r.histogram("lat", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(2.0)
    text = r.prometheus_text()
    assert "# HELP reqs total requests" in text
    assert "# TYPE reqs counter" in text
    assert 'reqs{engine="e0"} 2' in text
    assert "# TYPE lat histogram" in text
    assert 'lat_bucket{le="0.1"} 1' in text
    assert 'lat_bucket{le="1"} 2' in text
    assert 'lat_bucket{le="+Inf"} 3' in text
    assert "lat_count 3" in text
    assert "lat_sum 2.55" in text


def test_histogram_percentiles_are_deterministic():
    samples = list(np.random.default_rng(0).uniform(0.001, 0.2, 500))
    got = []
    for _ in range(2):
        h = Histogram("lat")
        for s in samples:
            h.observe(s)
        got.append((h.percentile(50), h.percentile(95), h.percentile(99)))
    assert got[0] == got[1], "same observations -> same percentiles, exactly"
    want = np.percentile(samples, [50, 95, 99], method="linear")
    np.testing.assert_allclose(got[0], want, rtol=1e-12)
    s = h.summary()
    assert s["count"] == 500 and s["p50"] == got[0][0] \
        and s["p95"] == got[0][1] and s["p99"] == got[0][2]


def test_metrics_view_read_through_and_reset():
    r = MetricsRegistry()
    v = MetricsView({"hits": r.counter("v_hits"), "rows": r.counter("v_rows")},
                    engine="e9")
    v["hits"] += 2                     # += decomposes to read + set
    v["rows"] = 10
    assert v["hits"] == 2 and isinstance(v["hits"], int)
    assert r.counter("v_hits").value(engine="e9") == 2, "writes hit the metric"
    assert v.setdefault("hits", 0) == 2, "setdefault is a no-op read"
    with pytest.raises(KeyError):
        v.setdefault("nope", 0)
    with pytest.raises(KeyError):
        v["nope"] = 1
    assert dict(v) == {"hits": 2, "rows": 10}
    other = MetricsView({"hits": r.counter("v_hits")}, engine="e10")
    other["hits"] = 5
    v.reset()                          # zeroes THIS label set only
    assert v["hits"] == 0 and other["hits"] == 5


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------

def test_tracer_disabled_records_nothing():
    t = Tracer()
    with t.span("x"):
        pass
    t.instant("y")
    assert t.events == []


def test_tracer_nested_spans_export_round_trip(tmp_path):
    t = Tracer()
    t.enable()
    with t.span("outer", cat="serve", rows=4) as sp:
        with t.span("inner", cat="serve"):
            pass
        sp.set(groups=2)
    path = tmp_path / "trace.json"
    doc = json.loads(t.export_chrome_json(str(path)))
    assert doc == json.loads(path.read_text()), "file matches the return"
    evs = doc["traceEvents"]
    assert [e["name"] for e in evs] == ["outer", "inner"]
    for e in evs:
        assert set(e) == {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                          "args"}
        assert e["ph"] == "X" and e["ts"] >= 0
    outer, inner = evs
    assert outer["args"] == {"rows": 4, "groups": 2}, "set() lands in args"
    # nesting is interval containment on the (pid, tid) track
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6


def test_enabled_scope_restores_state():
    t = Tracer()
    with t.enabled_scope():
        assert t.enabled
        with t.span("in-scope"):
            pass
    assert not t.enabled
    assert t.span_names() == ["in-scope"]


def test_compile_emits_stage_spans(small_inr):
    _, f, x = small_inr
    with TRACER.enabled_scope():
        P.compile_gradient(f, 1, x, config=HW)
    names = set(TRACER.span_names())
    assert {"compile", "compile.trace", "compile.passes",
            "compile.segment_plan", "compile.region_plan",
            "compile.codegen"} <= names
    # the compile span contains its stages
    ev = {e.name: e for e in TRACER.events}
    top, stage = ev["compile"], ev["compile.trace"]
    assert top.ts_ns <= stage.ts_ns
    assert stage.ts_ns + stage.dur_ns <= top.ts_ns + top.dur_ns


def test_serve_async_trace_has_nested_serve_spans(small_inr, tmp_path):
    cfg, f, x = small_inr
    cg = P.compile_gradient(f, 1, x, config=HW)
    eng = AsyncServingEngine(tmp_path / "a")
    eng.register("i0", cg)
    q = jax.random.uniform(jax.random.PRNGKey(5),
                           (70, cfg.in_features), jnp.float32, -1, 1)
    with TRACER.enabled_scope():
        eng.submit("i0", q)
        eng.drain()
    names = set(TRACER.span_names())
    assert "serve.retire" in names and "serve.unpad" in names
    assert names & {"serve.chunk", "serve.chunk.multi", "serve.block"}, names
    assert "serve.dispatch" in names and "serve.pad" in names
    doc = json.loads(TRACER.export_chrome_json())
    assert all(e["ph"] == "X" for e in doc["traceEvents"])


def _profiled(fn, trace_dir):
    """Run ``fn`` under a JAX profiler session; the program's spans the
    trace holds on its host planes, as (name, start_ns, end_ns) in start
    order."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(trace_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    data = ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for plane in data.planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith(("pipeline.", "serve.", "fit."))),
                  key=lambda s: s[1])


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_serve_spans_reach_the_profiler(small_inr, tmp_path):
    """With the in-memory record off, a profiler session still gets every
    engine and pipeline span: one ``pipeline.chunk`` per binary part of
    the request's block count (75 rows, 10 blocks = 8 + 2: two), each
    inside ``serve.dispatch``, no ``pipeline.block``, and ``serve.wait``
    after the enqueue."""
    cfg, f, x = small_inr
    cg = P.compile_gradient(f, 1, x, config=HW)
    eng = ServingEngine()
    eng.register("i0", cg)
    q = jax.random.uniform(jax.random.PRNGKey(8),
                           (75, cfg.in_features), jnp.float32, -1, 1)
    eng.serve([("i0", q)])                         # compile outside the trace
    spans = _profiled(lambda: eng.serve([("i0", q)]), tmp_path / "prof")
    assert TRACER.events == [], "the in-memory record stays off"
    names = [s[0] for s in spans]
    assert {"serve.group", "serve.pad", "serve.dispatch", "serve.wait",
            "serve.unpad", "pipeline.pad", "pipeline.chunk",
            "pipeline.stitch"} <= set(names)
    n_blocks = -(-75 // cg.config.block)
    assert n_blocks == 10
    chunks = [s for s in spans if s[0] == "pipeline.chunk"]
    assert len(chunks) == len(P.binary_parts(n_blocks)) == 2
    assert "pipeline.block" not in names
    (dispatch,) = [s for s in spans if s[0] == "serve.dispatch"]
    (wait,) = [s for s in spans if s[0] == "serve.wait"]
    assert all(_inside(c, dispatch) for c in chunks)
    assert dispatch[2] <= wait[1], "the wait opens once the work is enqueued"


def test_fit_step_spans_nest_and_count(tmp_path, monkeypatch):
    """Each fit step opens ``fit.step`` holding ``fit.gather``,
    ``fit.dispatch`` and ``fit.sync`` in that order; the loss reaches the
    host inside ``fit.sync``, and the step counts on ``fit_steps`` after
    it, still inside ``fit.step``."""
    import time

    import repro.fit.engine as fit_engine
    from repro.fit import ValueMSE, compile_fit, fit
    syncs, counts = [], []

    def timed_float(x):
        if isinstance(x, jax.Array):           # a device value to the host
            syncs.append(time.perf_counter_ns())
        return float(x)

    inc = fit_engine._FIT_STEPS.inc

    def timed_inc(*a, **kw):
        counts.append(time.perf_counter_ns())
        return inc(*a, **kw)

    monkeypatch.setattr(fit_engine, "float", timed_float, raising=False)
    monkeypatch.setattr(fit_engine._FIT_STEPS, "inc", timed_inc)
    cfg = SirenConfig(hidden_features=16, hidden_layers=1)
    params = siren_init(cfg, jax.random.PRNGKey(0))
    coords = jax.random.uniform(jax.random.PRNGKey(9),
                                (40, cfg.in_features), jnp.float32, -1, 1)
    target = jnp.tanh(3.0 * coords[:, :1])
    cf = compile_fit(siren_fn(cfg, params), ValueMSE(), 1, coords[:16],
                     params=params, config=HW)
    steps = REGISTRY.get("fit_steps")
    before = steps.value()
    with TRACER.enabled_scope():
        spans = _profiled(lambda: fit(cf, coords, target, steps=3,
                                      batch_rows=16), tmp_path / "prof")
    assert steps.value() - before == 3
    step_spans = [s for s in spans if s[0] == "fit.step"]
    assert len(step_spans) == 3
    for st in step_spans:
        kids = [s[0] for s in spans if s[0] != "fit.step" and _inside(s, st)]
        assert kids == ["fit.gather", "fit.dispatch", "fit.sync"]
    # the same spans on the host clock, against the loss reads and counts
    mem = {n: [(e.ts_ns, e.ts_ns + e.dur_ns) for e in TRACER.events
               if e.name == n] for n in ("fit.step", "fit.sync")}
    assert len(syncs) == len(counts) == 3
    for (a, b), (sa, sb), t_sync, t_count in zip(
            mem["fit.step"], mem["fit.sync"], syncs, counts):
        assert sa <= t_sync <= sb <= t_count <= b


# ---------------------------------------------------------------------------
# migrated stats surfaces
# ---------------------------------------------------------------------------

def test_engine_stats_live_on_registry(small_inr, tmp_path):
    cfg, f, x = small_inr
    cg = P.compile_gradient(f, 1, x, config=HW)
    eng = ServingEngine(tmp_path / "s")
    eng.register("i0", cg)
    q = jax.random.uniform(jax.random.PRNGKey(6),
                           (11, cfg.in_features), jnp.float32, -1, 1)
    eng.serve([("i0", q)])
    lab = eng.stats.labels["engine"]
    assert eng.stats["requests"] == 1
    assert REGISTRY.get("serve_requests").value(engine=lab) == 1
    assert REGISTRY.get("serve_rows").value(engine=lab) == 11
    h = REGISTRY.get("serve_batch_latency_s")
    assert h.count(engine=lab) == 1
    # a fresh engine gets a fresh label, starting from zero
    eng2 = ServingEngine(tmp_path / "s2")
    assert eng2.stats["requests"] == 0
    assert eng2.stats.labels["engine"] != lab


def test_compile_and_store_stats_on_registry(small_inr, tmp_path):
    _, f, x = small_inr
    P.compile_gradient(f, 1, x, config=HW, store=tmp_path / "st")
    info = P.compile_cache_info()
    assert info["misses"] >= 1 and info["store_puts"] >= 1
    assert REGISTRY.get("compile_cache_misses").value() == info["misses"]
    assert REGISTRY.get("compile_store_puts").value() == info["store_puts"]
    P.clear_compile_cache()
    assert P.compile_cache_info()["misses"] == 0
    assert REGISTRY.get("compile_cache_misses").value() == 0
    from repro.serve.store import ArtifactStore
    st = ArtifactStore(tmp_path / "st2")
    lab = st.stats.labels["store"]
    assert st.lookup("nope") is None
    assert st.stats["index_misses"] == 1
    assert REGISTRY.get("store_index_misses").value(store=lab) == 1
    assert st.info()["index_misses"] == 1, "info() reads through the view"


def test_autoconfig_counters_move(small_inr):
    _, f, x = small_inr
    before = REGISTRY.get("autoconfig_searches")
    n0 = before.value() if before else 0
    P.compile_gradient(f, 1, x, config="auto")
    assert REGISTRY.get("autoconfig_searches").value() == n0 + 1
    assert REGISTRY.get("autoconfig_candidates").value() > 0


# ---------------------------------------------------------------------------
# drift reports + FIFO headroom
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", [1, 2, 3])
def test_fifo_high_water_within_configured_depths(small_inr, order):
    """Runtime high-water occupancy never exceeds the FIFO pass's
    configured depths on the seed graphs — the deadlock-freedom guarantee
    has runtime evidence."""
    from repro.obs.drift import fifo_high_water

    _, f, x = small_inr
    cg = P.compile_gradient(f, order, x, config=HW)
    df = cg.dataflow_summary()
    configured = df["fifo"].depths_after
    high = fifo_high_water(df["design"], configured)
    assert set(high) == set(configured)
    for s, hw in high.items():
        assert 0 < hw <= configured[s], \
            f"stream {s}: high-water {hw} > configured {configured[s]}"


def test_drift_report_fields_and_json(small_inr):
    from repro.obs import DriftReport, drift_report

    _, f, x = small_inr
    cg = P.compile_gradient(f, 2, x, config=HW)
    assert cg.perf_model, "compile attaches the perf model"
    for m in cg.perf_model:
        assert m["predicted_row_cycles"] > 0
        assert m["modeled_hbm_bytes_block"] > 0
    rep = drift_report(cg, iters=2, warmup=1)
    assert isinstance(rep, DriftReport)
    assert rep.order == 2 and rep.block == HW.block
    assert len(rep.units) == len(cg.perf_model)
    assert abs(sum(u.predicted_share for u in rep.units) - 1.0) < 1e-9
    assert abs(sum(u.measured_share for u in rep.units) - 1.0) < 1e-9
    assert all(u.drift > 0 for u in rep.units)
    assert rep.min_headroom >= 0
    doc = json.dumps(rep.as_dict())
    back = json.loads(doc)
    assert back["max_drift"] == rep.max_drift
    assert len(back["units"]) == len(rep.units)
    assert "DriftReport" in rep.describe()


def test_drift_report_uses_supplied_coords(small_inr):
    from repro.obs import drift_report

    cfg, f, x = small_inr
    cg = P.compile_gradient(f, 1, x, config=HW)
    rep = drift_report(cg, x, iters=1, warmup=1)
    assert rep.total_measured_s > 0


# ---------------------------------------------------------------------------
# overhead gate
# ---------------------------------------------------------------------------

def test_telemetry_overhead_within_bound(small_inr, tmp_path):
    """Serving with tracing + metrics enabled stays within 5% wall (plus a
    small absolute epsilon for timer noise at this scale) of disabled."""
    import time

    cfg, f, x = small_inr
    cg = P.compile_gradient(f, 1, x, config=HW)
    eng = ServingEngine(tmp_path / "s")
    eng.register("i0", cg)
    reqs = [("i0", jax.random.uniform(jax.random.PRNGKey(40 + i),
                                      (48, cfg.in_features), jnp.float32,
                                      -1, 1)) for i in range(4)]
    eng.serve(reqs)                                # warm the jit caches

    def round_(enabled: bool) -> float:
        if enabled:
            TRACER.enable()
        else:
            TRACER.disable()
        t0 = time.perf_counter()
        eng.serve(reqs)
        return time.perf_counter() - t0

    on, off = [], []
    for _ in range(5):                             # interleave to decorrelate
        off.append(round_(False))
        on.append(round_(True))
    TRACER.disable()
    t_on, t_off = min(on), min(off)
    assert t_on <= t_off * 1.05 + 0.005, \
        f"telemetry overhead {t_on / t_off:.3f}x exceeds 5% ({t_on:.4f}s " \
        f"vs {t_off:.4f}s)"


# ---------------------------------------------------------------------------
# structured logger
# ---------------------------------------------------------------------------

def test_logger_quiet_under_pytest(capsys):
    assert obslog.current_level() == "error", "pytest detection"
    log = obslog.get_logger("train")
    log.info("step", step=1, loss=0.5)
    log.warn("straggler", step=2)
    assert capsys.readouterr() == ("", "")
    log.error("boom", code=3)
    out = capsys.readouterr()
    assert out.out == "" and out.err == "[train] boom code=3\n"


def test_logger_level_override(capsys):
    obslog.set_level("debug")
    try:
        log = obslog.get_logger("dryrun")
        log.info("cell ok", compile_s=1.25)
        assert capsys.readouterr().out == "[dryrun] cell ok compile_s=1.25\n"
        obslog.set_level("off")
        log.error("hidden")
        assert capsys.readouterr() == ("", "")
        with pytest.raises(ValueError):
            obslog.set_level("verbose")
    finally:
        obslog.set_level(None)
    assert obslog.current_level() == "error"
