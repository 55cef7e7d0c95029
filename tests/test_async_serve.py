"""Async serving engine (DESIGN.md §8): double-buffered dispatch parity,
continuous batching at chunk boundaries, K-axis sharding, LRU-bounded
engine caches, perf counters, and the cross-shard dataflow oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.siren import SirenConfig
from repro.core import pipeline as P
from repro.core.config import DEFAULT_CONFIG
from repro.inr.siren import siren_fn, siren_init
from repro.serve import AsyncServingEngine, ServingEngine
from tests.conftest import run_with_devices


@pytest.fixture(autouse=True)
def fresh_cache():
    P.clear_compile_cache()
    yield
    P.clear_compile_cache()


HW = DEFAULT_CONFIG.replace(block=8, chunk_blocks=4)


@pytest.fixture(scope="module")
def fleet():
    """Four INRs of one architecture + one of a second architecture."""
    cfg = SirenConfig(hidden_features=16, hidden_layers=1)
    x = jax.random.uniform(jax.random.PRNGKey(1),
                           (16, cfg.in_features), jnp.float32, -1, 1)
    cgs = [P.compile_gradient(siren_fn(cfg, siren_init(
        cfg, jax.random.PRNGKey(k))), 1, x, config=HW) for k in range(4)]
    wide = SirenConfig(hidden_features=24, hidden_layers=1)
    other = P.compile_gradient(siren_fn(wide, siren_init(
        wide, jax.random.PRNGKey(9))), 1, x, config=HW)
    return cfg, cgs, other


def _register(engine, cgs, other):
    for k, cg in enumerate(cgs):
        engine.register(f"i{k}", cg)
    engine.register("w0", other)
    return engine


def _assert_bit_identical(want, got):
    assert len(want) == len(got)
    for w, g in zip(want, got):
        assert len(w) == len(g)
        for a, b in zip(w, g):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# async parity
# ---------------------------------------------------------------------------

def test_async_bit_identical_mixed_stream(fleet, tmp_path):
    """serve_async over a mixed single/multi-INR stream with non-block-
    multiple row counts returns BIT-IDENTICAL results to the sync path, in
    request order (the ISSUE-6 acceptance bar)."""
    cfg, cgs, other = fleet
    sync = _register(ServingEngine(tmp_path / "s"), cgs, other)
    asyn = _register(AsyncServingEngine(tmp_path / "a"), cgs, other)
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(14):
        inr = ["i0", "i1", "w0", "i2", "i0", "i3", "w0"][i % 7]
        n = int(rng.integers(1, 75))           # spans chunk boundaries,
        q = jax.random.uniform(jax.random.PRNGKey(200 + i),   # never a
                               (n, cfg.in_features),          # block multiple
                               jnp.float32, -1, 1)            # by design
        reqs.append((inr, q))
    _assert_bit_identical(sync.serve(reqs), asyn.serve_async(reqs))
    # the stream actually exercised the async machinery: chunks coalesced
    # across requests, both dispatch kinds used, queue depth bounded at 2
    st = asyn.stats
    assert st["async_chunks"] + st["async_multi_chunks"] > 0
    assert 1 <= st["max_inflight"] <= asyn.inflight == 2


def test_async_single_stream_coalesces_chunks(fleet, tmp_path):
    """Many small requests for ONE INR coalesce into full chunks: far fewer
    dispatches than requests, still bit-identical."""
    cfg, cgs, other = fleet
    sync = _register(ServingEngine(tmp_path / "s"), cgs, other)
    asyn = _register(AsyncServingEngine(tmp_path / "a"), cgs, other)
    qs = [jax.random.uniform(jax.random.PRNGKey(300 + i),
                             (13, cfg.in_features), jnp.float32, -1, 1)
          for i in range(20)]                 # 260 rows, chunk = 32 rows
    want = sync.serve([("i0", q) for q in qs])
    tickets = [asyn.submit("i0", q) for q in qs]
    assert tickets == list(range(20))
    got = asyn.drain()
    _assert_bit_identical(want, got)
    st = asyn.stats
    assert st["async_chunks"] == (20 * 13) // (HW.chunk_blocks * HW.block)
    assert st["async_chunks"] + st["async_blocks"] < len(qs)
    assert asyn.pending_rows() == 0


def test_mid_stream_admission_returns_in_order(fleet, tmp_path):
    """A request admitted mid-stream (after chunks of an earlier request
    already dispatched) joins the lane set at the next chunk boundary and
    still gets its results at its own ticket position."""
    cfg, cgs, other = fleet
    asyn = _register(AsyncServingEngine(tmp_path / "a"), cgs, other)
    sync = _register(ServingEngine(tmp_path / "s"), cgs, other)
    q_big = jax.random.uniform(jax.random.PRNGKey(0),
                               (90, cfg.in_features), jnp.float32, -1, 1)
    q_mid = jax.random.uniform(jax.random.PRNGKey(1),
                               (17, cfg.in_features), jnp.float32, -1, 1)
    q_new = jax.random.uniform(jax.random.PRNGKey(2),
                               (21, cfg.in_features), jnp.float32, -1, 1)
    t0 = asyn.submit("i0", q_big)      # full chunks dispatch immediately
    assert asyn.stats["async_chunks"] >= 1, "chunks dispatch before drain"
    t1 = asyn.submit("i1", q_mid)      # admitted mid-stream -> multi lanes
    t2 = asyn.submit("i0", q_new)
    assert (t0, t1, t2) == (0, 1, 2)
    got = asyn.drain()
    assert len(got) == 3
    assert got[0][0].shape[0] == 90 and got[1][0].shape[0] == 17 \
        and got[2][0].shape[0] == 21
    want = sync.serve([("i0", q_big), ("i1", q_mid), ("i0", q_new)])
    for w, g in zip(want, got):
        for a, b in zip(w, g):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
    assert asyn.stats["admissions"] >= 2 and asyn.stats["evictions"] >= 2


def test_drain_is_incremental(fleet, tmp_path):
    """drain() returns only the tickets since the last drain; the engine
    is reusable across rounds."""
    cfg, cgs, other = fleet
    asyn = _register(AsyncServingEngine(tmp_path / "a"), cgs, other)
    q = jax.random.uniform(jax.random.PRNGKey(4),
                           (11, cfg.in_features), jnp.float32, -1, 1)
    asyn.submit("i0", q)
    first = asyn.drain()
    assert len(first) == 1
    asyn.submit("i1", q)
    asyn.submit("i2", q)
    second = asyn.drain()
    assert len(second) == 2
    assert asyn.drain() == []


def test_pending_take_reuses_one_slice_program():
    """A long request taken chunk by chunk leaves its lane through slices
    at a dynamic offset: the rows come out in order, and the compiles stay
    a constant handful instead of one per remainder shape."""
    from repro.serve.async_engine import _Pending

    compiles = []

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    coords = jnp.arange(41 * 16 * 2, dtype=jnp.float32).reshape(-1, 2)
    p = _Pending()
    p.push(0, coords)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        taken = [p.take(16)[0] for _ in range(40)]
        jax.block_until_ready(taken)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    np.testing.assert_array_equal(np.concatenate(taken),
                                  np.asarray(coords[:640]))
    assert p.rows == 16
    assert len(compiles) <= 2, len(compiles)


def test_empty_request_and_serve_async_empty(fleet, tmp_path):
    """A zero-row request never reaches a lane (it would change the lane
    count of the dispatch group) yet still gets a well-formed 0-row result
    at its ticket position."""
    cfg, cgs, other = fleet
    asyn = _register(AsyncServingEngine(tmp_path / "a"), cgs, other)
    sync = _register(ServingEngine(tmp_path / "s"), cgs, other)
    q0 = jnp.zeros((0, cfg.in_features), jnp.float32)
    q1 = jax.random.uniform(jax.random.PRNGKey(5),
                            (7, cfg.in_features), jnp.float32, -1, 1)
    # group the sync side the way the async lanes form: the empty request
    # contributes no lane, so i1 serves alone
    want = sync.serve([("i0", q0)]) + sync.serve([("i1", q1)])
    got = asyn.serve_async([("i0", q0), ("i1", q1)])
    _assert_bit_identical(want, got)
    assert got[0][0].shape[0] == 0
    assert asyn.serve_async([]) == []


# ---------------------------------------------------------------------------
# LRU caches + perf counters
# ---------------------------------------------------------------------------

def test_engine_caches_are_lru_bounded(fleet, tmp_path):
    """_payloads/_multi evict least-recently-used past capacity (payloads
    only when a store can reload them) and count evictions in stats."""
    cfg, cgs, other = fleet
    e = _register(ServingEngine(tmp_path / "s", payload_cache=3,
                                multi_cache=2), cgs, other)
    assert len(e._payloads) <= 3
    assert e.stats["payload_evictions"] >= 2    # 5 registered, cap 3
    q = jax.random.uniform(jax.random.PRNGKey(6),
                           (9, cfg.in_features), jnp.float32, -1, 1)
    # three distinct multi-lane sets -> the first stack is evicted
    e.serve([("i0", q), ("i1", q)])
    e.serve([("i1", q), ("i2", q)])
    e.serve([("i2", q), ("i3", q)])
    assert len(e._multi) <= 2
    assert e.stats["multi_evictions"] >= 1
    # an evicted payload reloads from the store transparently
    out = e.serve([("i1", q)])
    assert out[0][0].shape[0] == 9


def test_payloads_not_evicted_without_store(fleet):
    """With no store attached an evicted payload would be the ONLY copy of
    the weights — the cache must grow instead."""
    cfg, cgs, other = fleet
    e = ServingEngine(payload_cache=2)
    for k, cg in enumerate(cgs):
        e.register(f"i{k}", cg)
    assert len(e._payloads) == 4 > e._payloads.cap
    assert e.stats["payload_evictions"] == 0


def test_perf_counters_populate(fleet, tmp_path, monkeypatch):
    """Both paths open their phase spans (the sync path enqueues, then
    blocks only inside ``serve.wait``; the async path retires what it
    dispatched), the queue-wait histogram moves on the async path only,
    and describe() reports the async queue."""
    import time

    from repro.obs.metrics import REGISTRY
    from repro.obs.tracing import TRACER
    cfg, cgs, other = fleet
    sync = _register(ServingEngine(tmp_path / "s"), cgs, other)
    asyn = _register(AsyncServingEngine(tmp_path / "a"), cgs, other)
    q = jax.random.uniform(jax.random.PRNGKey(7),
                           (40, cfg.in_features), jnp.float32, -1, 1)
    blocks = []
    block = jax.block_until_ready

    def timed_block(x):
        blocks.append(time.perf_counter_ns())
        return block(x)

    monkeypatch.setattr(jax, "block_until_ready", timed_block)
    TRACER.clear()
    with TRACER.enabled_scope():
        sync.serve([("i0", q), ("i1", q)])
        sync_spans = TRACER.span_names()
        waits = [(e.ts_ns, e.ts_ns + e.dur_ns) for e in TRACER.events
                 if e.name == "serve.wait"]
        TRACER.clear()
        asyn.serve_async([("i0", q), ("i1", q)])
        async_spans = TRACER.span_names()
    TRACER.clear()
    serve_spans = [n for n in sync_spans if n.startswith("serve.")]
    assert serve_spans[:4] == ["serve.group", "serve.pad", "serve.dispatch",
                               "serve.wait"]
    sync_blocks = [t for t in blocks if t <= waits[-1][1]]
    assert sync_blocks and all(any(a <= t <= b for a, b in waits)
                               for t in sync_blocks)
    assert "serve.unpad" in sync_spans and "serve.retire" not in sync_spans
    assert {"serve.pad", "serve.dispatch", "serve.retire",
            "serve.unpad"} <= set(async_spans)
    assert "serve.wait" not in async_spans
    waits = REGISTRY.get("serve_queue_wait_latency_s")
    assert waits.count(engine=sync.stats.labels["engine"]) == 0
    assert waits.count(engine=asyn.stats.labels["engine"]) > 0
    for e in (sync, asyn):
        assert not {"host_group_s", "device_exec_s", "queue_wait_s",
                    "host_unpad_s"} & set(e.stats)
    assert "async: inflight" in asyn.describe()


# ---------------------------------------------------------------------------
# K-axis sharding
# ---------------------------------------------------------------------------

def test_k_axis_sharding_parity_two_devices():
    """On a 2-device CPU mesh the multi-INR K axis is sharded (weights
    split across devices, rows per-shard-local) with numerics matching the
    unsharded engine — sync AND async paths (subprocess: forced host
    devices)."""
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import Mesh
from repro.configs.siren import SirenConfig
from repro.core import pipeline as P
from repro.core.config import DEFAULT_CONFIG
from repro.distributed.sharding import ShardingPolicy
from repro.inr.siren import siren_fn, siren_init
from repro.serve import AsyncServingEngine, ServingEngine

assert len(jax.devices()) == 2
cfg = SirenConfig(hidden_features=16, hidden_layers=1)
x = jax.random.uniform(jax.random.PRNGKey(1), (16, cfg.in_features),
                       jnp.float32, -1, 1)
hw = DEFAULT_CONFIG.replace(block=8, chunk_blocks=4)
cgs = [P.compile_gradient(siren_fn(cfg, siren_init(
    cfg, jax.random.PRNGKey(k))), 1, x, config=hw) for k in range(4)]
d = tempfile.mkdtemp()
pol = ShardingPolicy(Mesh(np.array(jax.devices()), ("data",)))

plain = ServingEngine(d + "/p")
shard = ServingEngine(d + "/s", sharding=pol)
asyn = AsyncServingEngine(d + "/a", sharding=pol)
for k in range(4):
    for e in (plain, shard, asyn):
        e.register(f"i{k}", cgs[k])
reqs = [(f"i{k}", jax.random.uniform(jax.random.PRNGKey(50 + k),
                                     (n, cfg.in_features), jnp.float32,
                                     -1, 1))
        for k, n in enumerate([21, 34, 9, 40])]
want = plain.serve(reqs)
for got, eng in ((shard.serve(reqs), shard),
                 (asyn.serve_async(reqs), asyn)):
    for w, g in zip(want, got):
        for a, b in zip(w, g):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
    assert eng.stats["k_sharded_batches"] >= 1, eng.stats
m = shard._multi_artifact(cgs[0].signature, ("i0", "i1", "i2", "i3"))
assert m.k_sharded
sh = m.residents[next(iter(m.residents))].sharding
assert len(sh.device_set) == 2, "stacked residents live on both devices"

# K=3 does NOT divide the 2-device axis -> divisibility fallback
# replicates: not sharded, numerics unchanged
m3 = shard._multi_artifact(cgs[0].signature, ("i0", "i1", "i2"))
assert not m3.k_sharded
got3 = shard.serve(reqs[:3])
for w, g in zip(want[:3], got3):
    for a, b in zip(w, g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
print("K-shard parity OK")
""", n=2)


def test_k_sharding_trivial_on_one_device(fleet, tmp_path):
    """A 1-device mesh exercises the K-sharded placement path end to end
    (device_put with a NamedSharding over one device) and must be a
    numeric no-op — the multi-device behavior is the same code under SPMD
    partitioning."""
    from jax.sharding import Mesh
    from repro.distributed.sharding import ShardingPolicy
    from repro.serve import MultiINRArtifact
    from repro.serve.multi_inr import const_payload

    cfg, cgs, other = fleet
    pol = ShardingPolicy(Mesh(np.array(jax.devices()[:1]), ("data",)))
    m = MultiINRArtifact(cgs[0], [const_payload(cgs[0])], ["a"],
                         sharding=pol)
    assert m.k_sharded                        # 1 % 1 == 0: trivially sharded
    q = jax.random.uniform(jax.random.PRNGKey(8),
                           (9, cfg.in_features), jnp.float32, -1, 1)
    want = cgs[0].apply_batched(q)
    got = m.apply_batched(q)
    for a, b in zip(want, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b[0]),
                                   rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# cross-shard dataflow oracle
# ---------------------------------------------------------------------------

def test_cross_shard_edge_in_dataflow_oracle(fleet):
    """n_shards > 1 adds the cross-shard input stream as one more FIFO
    edge: an xshard forwarder process, one extra stream, strictly larger
    modeled latency, still deadlock-free."""
    from repro.core.dataflow import DataflowGraph, map_to_dataflow

    _, cgs, _ = fleet
    cg = cgs[0]
    base = map_to_dataflow(cg.graph, plan=cg.plan, config=cg.config)
    sharded_cfg = cg.config.replace(n_shards=2, xshard_row_cost=3)
    sh = map_to_dataflow(cg.graph, plan=cg.plan, config=sharded_cfg)
    assert len(sh.streams) == len(base.streams) + len(cg.plan.inputs)
    assert any(p.name.startswith("xshard") for p in sh.processes)
    assert not any(p.name.startswith("xshard") for p in base.processes)
    lat = {}
    for name, design in (("base", base), ("sharded", sh)):
        dead, latency, _ = DataflowGraph(design).check(
            {s: 10**6 for s in design.streams})
        assert not dead
        lat[name] = latency
    assert lat["sharded"] > lat["base"], "interconnect hop must cost latency"


def test_auto_config_under_sharded_mesh(fleet):
    """config='auto' seeded with an n_shards base passes the deadlock
    check with the cross-shard edge modeled, and the winner keeps
    n_shards (the ISSUE-6 acceptance criterion)."""
    from repro.core.dataflow import DataflowGraph

    cfg = SirenConfig(hidden_features=16, hidden_layers=1)
    f = siren_fn(cfg, siren_init(cfg, jax.random.PRNGKey(11)))
    x = jax.random.uniform(jax.random.PRNGKey(1),
                           (16, cfg.in_features), jnp.float32, -1, 1)
    cg = P.compile_gradient(
        f, 1, x, config="auto",
        base_config=DEFAULT_CONFIG.replace(n_shards=2))
    assert cg.config.n_shards == 2
    assert cg.autoconfig is not None
    assert all(not c.deadlocked for c in cg.autoconfig.candidates
               if c.accepted)
    summary = cg.dataflow_summary()
    design = summary["design"]
    assert any(p.name.startswith("xshard") for p in design.processes), \
        "winner's dataflow design models the cross-shard stream"
    dead, _, _ = DataflowGraph(design).check(summary["fifo"].depths_after)
    assert not dead
    # base_config is an auto-mode knob only
    with pytest.raises(ValueError):
        P.compile_gradient(f, 1, x,
                           base_config=DEFAULT_CONFIG.replace(n_shards=2))


# ---------------------------------------------------------------------------
# bank-aware request batching (ISSUE-10 satellite)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bank_setup():
    from repro.configs.siren import InspConfig
    from repro.inr.gradnet import num_features
    from repro.inr.insp import insp_head, insp_init

    cfg = SirenConfig(hidden_features=16, hidden_layers=1)
    f = siren_fn(cfg, siren_init(cfg, jax.random.PRNGKey(0)))
    x = jax.random.uniform(jax.random.PRNGKey(1),
                           (16, cfg.in_features), jnp.float32, -1, 1)
    icfg = InspConfig(hidden=8, layers=2, grad_order=1)
    nf = num_features(cfg.in_features, cfg.out_features, 1)
    heads = [insp_head(insp_init(icfg, nf, 1, jax.random.PRNGKey(i + 1)))
             for i in range(3)]
    bank = P.compile_bank(f, heads, 1, x, config=HW)
    cg = P.compile_gradient(f, 1, x, config=HW)
    return cfg, bank, cg


def test_async_bank_parity_and_group_counter(bank_setup):
    """Filter requests of one bank coalesce into ONE concatenated pass per
    admission boundary — results bit-identical to the sync path, and the
    bank_groups counter advances in lockstep with it."""
    cfg, bank, cg = bank_setup

    def build(engine):
        engine.register("inr", cg)
        engine.register_bank(["fa", "fb", "fc"], bank)
        return engine

    def q(n, seed):
        return jax.random.uniform(jax.random.PRNGKey(seed),
                                  (n, cfg.in_features), jnp.float32, -1, 1)

    reqs = [("fa", q(13, 2)), ("inr", q(9, 3)), ("fb", q(21, 4)),
            ("fa", q(5, 5)), ("fc", q(0, 6))]
    sync = build(ServingEngine())
    want = sync.serve(reqs)
    asy = build(AsyncServingEngine())
    got = asy.serve_async(reqs)
    _assert_bit_identical(want, got)
    assert asy.stats["bank_groups"] == sync.stats["bank_groups"] == 1
    assert asy.pending_rows() == 0


def test_async_bank_chunk_dispatch_before_drain(bank_setup):
    """A bank lane that fills a serving chunk dispatches at submit time
    (the double-buffered path), not only at drain."""
    cfg, bank, cg = bank_setup
    asy = AsyncServingEngine()
    asy.register_bank(["fa", "fb", "fc"], bank)
    chunk_rows = bank.cg.config.chunk_blocks * bank.cg.config.block
    q = jax.random.uniform(jax.random.PRNGKey(7),
                           (chunk_rows, cfg.in_features), jnp.float32, -1, 1)
    asy.submit("fa", q)
    assert asy.stats["bank_groups"] == 1        # dispatched pre-drain
    asy.submit("fb", q[:7])
    res = asy.drain()
    assert asy.stats["bank_groups"] == 2
    sync = ServingEngine()
    sync.register_bank(["fa", "fb", "fc"], bank)
    want = sync.serve([("fa", q), ("fb", q[:7])])
    _assert_bit_identical(want, res)
