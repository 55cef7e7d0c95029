"""CompiledGradient front door: cache semantics (hit = same object, no
re-trace; changed key = recompile) and apply_batched parity with the
reference executor on non-block-multiple batches."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pipeline as P
from repro.core.executor import reference_executor
from repro.core.passes import optimize
from repro.core.trace import extract_graph
from repro.inr.gradnet import paper_gradients
from repro.configs.siren import SirenConfig
from repro.inr.siren import siren_fn, siren_init


@pytest.fixture(autouse=True)
def fresh_cache():
    P.clear_compile_cache()
    yield
    P.clear_compile_cache()


@pytest.fixture(scope="module")
def small_siren():
    cfg = SirenConfig(hidden_features=16, hidden_layers=1)
    params = siren_init(cfg, jax.random.PRNGKey(0))
    f = siren_fn(cfg, params)
    x = jax.random.uniform(jax.random.PRNGKey(1),
                           (16, cfg.in_features), jnp.float32, -1, 1)
    return cfg, f, x


def test_cache_hit_returns_same_artifact_without_retrace(small_siren,
                                                         monkeypatch):
    cfg, f, x = small_siren
    calls = []
    real = extract_graph

    def counting_extract(fn, *args, **kw):
        calls.append(fn)
        return real(fn, *args, **kw)

    # compile_gradient imports extract_graph lazily from repro.core.trace
    import repro.core.trace as T
    monkeypatch.setattr(T, "extract_graph", counting_extract)

    cg1 = P.compile_gradient(f, 2, x, block=8)
    assert len(calls) == 1
    cg2 = P.compile_gradient(f, 2, x, block=8)
    assert cg2 is cg1, "cache hit must return the identical artifact"
    assert len(calls) == 1, "cache hit must not re-trace"
    info = P.compile_cache_info()
    assert info["hits"] == 1 and info["misses"] == 1 and info["size"] == 1


def test_cache_recompiles_on_changed_key(small_siren):
    cfg, f, x = small_siren
    base = P.compile_gradient(f, 1, x, block=8)
    assert P.compile_gradient(f, 1, x, block=8) is base
    # changed order
    assert P.compile_gradient(f, 2, x, block=8) is not base
    # changed block
    assert P.compile_gradient(f, 1, x, block=4) is not base
    # changed coord shape
    x32 = jnp.zeros((32, cfg.in_features), x.dtype)
    assert P.compile_gradient(f, 1, x32, block=8) is not base
    # a different fn object (same math) is a different identity
    f2 = siren_fn(cfg, siren_init(cfg, jax.random.PRNGKey(0)))
    assert P.compile_gradient(f2, 1, x, block=8) is not base
    info = P.compile_cache_info()
    assert info["misses"] == 5 and info["hits"] == 1


def test_abstract_example_coords_compile(small_siren):
    """example_coords only contributes shape/dtype: a ShapeDtypeStruct works
    and shares the cache entry with a concrete array of the same aval."""
    cfg, f, x = small_siren
    s = jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
    cg = P.compile_gradient(f, 1, s, block=8)
    assert P.compile_gradient(f, 1, x, block=8) is cg
    # batch dims that round up to the same trace batch share the entry
    x13 = jnp.zeros((13, cfg.in_features), x.dtype)
    assert P.compile_gradient(f, 1, x13, block=8) is cg


@pytest.mark.parametrize("order", [1, 2, 3])
def test_apply_batched_matches_reference_on_unpadded_rows(small_siren, order):
    cfg, f, x = small_siren
    cg = P.compile_gradient(f, order, x, block=8)

    # 13 rows: not a block multiple — the serving path pads to 16 and the
    # padding must never reach the caller
    q = jax.random.uniform(jax.random.PRNGKey(2 + order),
                           (13, cfg.in_features), jnp.float32, -1, 1)
    got = cg.apply_batched(q)

    gfn = paper_gradients(f, order, cfg.out_features, cfg.in_features)
    g_ref = extract_graph(gfn, q)
    optimize(g_ref)
    want = reference_executor(g_ref)(q)

    assert len(got) == len(want)
    for a, b in zip(want, got):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_apply_batched_chunked_path(small_siren):
    """Batches large enough to hit the chunked lax.map path agree with the
    per-block path and the reference.  The chunk size is part of the
    artifact's HardwareConfig (not a per-call kwarg); ``apply_batched``
    splits a request into the binary parts of its block count, so each
    artifact compiles at most ``floor(log2(nb_max)) + 1`` map lengths
    regardless of the batch sizes served."""
    from repro.core.config import HardwareConfig

    cfg, f, x = small_siren
    cg_chunked = P.compile_gradient(
        f, 1, x, config=HardwareConfig(block=8, chunk_blocks=2))
    cg_blocks = P.compile_gradient(
        f, 1, x, config=HardwareConfig(block=8, chunk_blocks=10**9))
    assert cg_chunked is not cg_blocks, "distinct configs, distinct artifacts"
    q = jax.random.uniform(jax.random.PRNGKey(7),
                           (70, cfg.in_features), jnp.float32, -1, 1)
    got_chunked = cg_chunked.apply_batched(q)   # 9 blocks = 8 + 1
    got_blocks = cg_blocks.apply_batched(q)
    for a, b in zip(got_chunked, got_blocks):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    gfn = paper_gradients(f, 1, cfg.out_features, cfg.in_features)
    for a, b in zip(gfn(q), got_chunked):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_binary_parts():
    assert P.binary_parts(1) == [1]
    assert P.binary_parts(10) == [8, 2]
    assert P.binary_parts(10240) == [8192, 2048]
    assert P.binary_parts(2048) == [2048]
    assert all(sum(P.binary_parts(nb)) == nb for nb in range(1, 300))


def _dispatches() -> float:
    from repro.obs.metrics import REGISTRY
    return REGISTRY.get("pipeline_dispatches").value()


@pytest.mark.parametrize("n", [1, 7, 8, 75, 8 * 13 + 3, 8 * 64,
                               8 * 64 * 3 + 5])
def test_apply_batched_binary_parts_match_block_loop(small_siren, n):
    """A request dispatched as the binary parts of its block count equals
    ``apply_block`` looped over its edge-padded blocks, bitwise (the CPU
    backend computes the mapped and the single block alike), in
    ``popcount(ceil(n / block))`` dispatches."""
    cfg, f, x = small_siren
    cg = P.compile_gradient(f, 2, x, block=8)
    q = jax.random.uniform(jax.random.PRNGKey(n), (n, cfg.in_features),
                           jnp.float32, -1, 1)
    nb = -(-n // 8)
    padded = jnp.concatenate([q, jnp.broadcast_to(q[-1:], (nb * 8 - n,
                                                           cfg.in_features))])
    blocks = [cg.apply_block(padded[i * 8:(i + 1) * 8]) for i in range(nb)]
    want = [jnp.concatenate(col)[:n] for col in zip(*blocks)]

    before = _dispatches()
    got = cg.apply_batched(q)
    assert _dispatches() - before == bin(nb).count("1")
    assert len(got) == len(want)
    for a, b in zip(want, got):
        assert a.shape == b.shape
        np.testing.assert_array_equal(a, b)


def test_apply_batched_compiles_one_map_per_binary_part(small_siren):
    """Every request size from 1 to 40 blocks needs only the map lengths
    1, 2, 4, 8, 16 and 32: at most six compiled traces."""
    cfg, f, x = small_siren
    cg = P.compile_gradient(f, 1, x, block=8)
    q = jax.random.uniform(jax.random.PRNGKey(3), (8 * 40, cfg.in_features),
                           jnp.float32, -1, 1)
    for k in range(1, 41):
        cg.apply_batched(q[:8 * k])
    assert cg._chunk_apply._cache_size() <= 6


def test_artifact_carries_the_whole_pipeline(small_siren):
    """The artifact is the paper's end-to-end compiler output: optimized
    graph, plan, residents, dispatch, emitted source, dataflow summary."""
    cfg, f, x = small_siren
    cg = P.compile_gradient(f, 2, x, block=8)
    assert cg.plan.validate()
    assert cg.plan.graph is cg.graph
    assert cg.residents and all(
        nid in cg.plan.resident for nid in cg.residents)
    assert len(cg.dispatch) == len(cg.plan.segments)
    assert "def pipeline(" in cg.source
    assert "HARDWARE_CONFIG" in cg.source, "source records its config"
    summary = cg.dataflow_summary()
    assert summary["sum_depths_after"] <= summary["sum_depths_before"]
    assert cg.dataflow_summary() is summary, "dataflow summary is cached"
    # the cache is keyed by parameters: different arguments get their own
    # (correct) summary instead of silently reusing the first call's
    other = cg.dataflow_summary(mm_parallel=64)
    assert other is not summary
    assert cg.dataflow_summary(mm_parallel=64) is other


def test_streaming_executor_is_a_cache_wrapper(small_siren):
    """streaming_executor compiles-or-hits: same (graph, block, use_pallas)
    returns the same jitted apply."""
    from repro.core import executor as ex

    cfg, f, x = small_siren
    gfn = paper_gradients(f, 1, cfg.out_features, cfg.in_features)
    g = extract_graph(gfn, x)
    optimize(g)
    f1 = ex.streaming_executor(g, block=8, use_pallas=False)
    f2 = ex.streaming_executor(g, block=8, use_pallas=False)
    assert f1 is f2
    want = reference_executor(g)(x)
    for a, b in zip(want, f1(x)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
