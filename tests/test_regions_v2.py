"""Region scheduler v2 (DESIGN.md §7): liveness-based VMEM packing,
column-tiled megakernels, bcast_rows on-chip broadcasts, K-stacked
double-buffered resident serving, chunk_blocks in the autoconfig search,
and the calibrated dataflow row costs.

Covers the ISSUE-7 acceptance surface: peak-live <= sum-of-outputs on every
seed gradient graph, region cuts monotone in the VMEM budget, bn-tiled
parity on non-multiple widths (kernel-level and through the scheduler),
bit-exact fused-vs-interpreted-unfused serving at orders 1-2, and the
``load_op_row_cost`` round-trip against the committed calibration JSON.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.siren import SirenConfig
from repro.core import executor as ex
from repro.core import pipeline as P
from repro.core.config import HardwareConfig
from repro.core.passes import optimize
from repro.core.regions import (_lower_segment, _region_io, _vmem_estimate,
                                build_region_plan, plan_col_tiles,
                                region_hbm_bytes_per_block)
from repro.core.segment import build_segment_plan
from repro.core.trace import extract_graph
from repro.inr.gradnet import paper_gradients
from repro.inr.siren import siren_fn, siren_init
from repro.kernels.region import (RegionKernelSpec, TileGroup, region_call,
                                  region_call_stacked)


@pytest.fixture(autouse=True)
def fresh_cache():
    P.clear_compile_cache()
    yield
    P.clear_compile_cache()


@pytest.fixture(scope="module")
def small_siren():
    cfg = SirenConfig(hidden_features=32, hidden_layers=1)
    params = siren_init(cfg, jax.random.PRNGKey(0))
    f = siren_fn(cfg, params)
    x = jax.random.uniform(jax.random.PRNGKey(1),
                           (16, cfg.in_features), jnp.float32, -1, 1)
    return cfg, params, f, x


@pytest.fixture(scope="module")
def wide_siren():
    """hidden=80: wider than bn=32 and NOT a multiple of it (80 = 2*32+16),
    so column tiling runs with a ragged last tile."""
    cfg = SirenConfig(hidden_features=80, hidden_layers=1)
    params = siren_init(cfg, jax.random.PRNGKey(0))
    f = siren_fn(cfg, params)
    x = jax.random.uniform(jax.random.PRNGKey(1),
                           (16, cfg.in_features), jnp.float32, -1, 1)
    return cfg, params, f, x


def _graph(cfg, f, x, order):
    g = extract_graph(paper_gradients(f, order, cfg.out_features,
                                      cfg.in_features), x)
    optimize(g)
    return g


FUSED = HardwareConfig(block=8, use_pallas=True, fuse_regions=True)
INTERP_UNFUSED = HardwareConfig(block=8, use_pallas=False,
                                fuse_regions=False)


# -- liveness packing --------------------------------------------------------

@pytest.mark.parametrize("order", [1, 2, 3])
def test_peak_live_never_exceeds_sum(small_siren, order):
    """The liveness estimate is bounded by the PR 5 sum-of-outputs estimate
    on every fused region of every seed gradient graph: freeing outputs at
    their last use can only shrink the working set."""
    cfg, _, f, x = small_siren
    conf = FUSED.resolved()
    plan = build_segment_plan(_graph(cfg, f, x, order), config=conf)
    rplan = build_region_plan(plan, conf)
    assert rplan.fused_regions()
    for r in rplan.fused_regions():
        members = [(plan.segments[s],
                    _lower_segment(plan, plan.segments[s]))
                   for s in r.segments]
        io = _region_io(plan, members)
        live = _vmem_estimate(plan, io, conf, packing="live")
        total = _vmem_estimate(plan, io, conf, packing="sum")
        assert live <= total, (r.id, live, total)


@pytest.mark.parametrize("order", [1, 2, 3])
def test_live_packing_fuses_at_least_as_much_as_sum(small_siren, order):
    """Under any shared budget, liveness packing never produces MORE regions
    than sum packing (the whole point: longer regions, fewer dispatches)."""
    cfg, _, f, x = small_siren
    g = _graph(cfg, f, x, order)
    for budget in (32 * 1024, 128 * 1024, 8 * 1024 * 1024):
        live_conf = FUSED.replace(vmem_budget=budget).resolved()
        sum_conf = live_conf.replace(region_packing="sum")
        plan = build_segment_plan(g, config=live_conf)
        n_live = len(build_region_plan(plan, live_conf).regions)
        n_sum = len(build_region_plan(plan, sum_conf).regions)
        assert n_live <= n_sum, (budget, n_live, n_sum)


def test_region_count_monotone_in_budget(small_siren):
    """Raising the VMEM budget never increases the region count: every cut
    the scheduler makes is forced by the budget (or a config cut point)."""
    cfg, _, f, x = small_siren
    g = _graph(cfg, f, x, 3)
    counts = []
    for budget in (16 * 1024, 32 * 1024, 64 * 1024, 256 * 1024,
                   8 * 1024 * 1024):
        conf = FUSED.replace(vmem_budget=budget).resolved()
        plan = build_segment_plan(g, config=conf)
        counts.append(len(build_region_plan(plan, conf).regions))
    assert counts == sorted(counts, reverse=True), counts


def test_peak_vmem_within_budget(small_siren):
    cfg, _, f, x = small_siren
    cg = P.compile_gradient(f, 3, x, config=FUSED)
    peak = cg.region_plan.peak_vmem_bytes()
    assert 0 < peak <= cg.config.vmem_budget


# -- bit-exactness of the untiled megakernel ---------------------------------

@pytest.mark.parametrize("order", [1, 2])
def test_fused_bitexact_vs_interpreted_unfused(small_siren, order):
    """The untiled region megakernel (bcast_rows included) is BIT-IDENTICAL
    to interpreted per-segment execution at orders 1-2 — fusion and on-chip
    row broadcasting reorder nothing."""
    cfg, _, f, x = small_siren
    fused = P.compile_gradient(f, order, x, config=FUSED)
    ref = P.compile_gradient(f, order, x, config=INTERP_UNFUSED)
    assert fused.region_plan.fused_regions()
    assert all(r.col_tiles == 1 for r in fused.region_plan.regions)
    for a, b in zip(ref.apply_batched(x), fused.apply_batched(x)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bcast_rows_populated_and_cut_hbm(small_siren):
    """Row-constant resident extras ride as ``bcast_rows`` (one [1, C] VMEM
    row) and the HBM model charges them nothing per block — strictly less
    traffic than the streamed-broadcast fallback would."""
    cfg, _, f, x = small_siren
    cg = P.compile_gradient(f, 2, x, config=FUSED)
    g = cg.graph
    rows = [(nid, c) for r in cg.region_plan.fused_regions()
            for nid, c in r.bcast_rows]
    assert rows, "order-2 SIREN gradients must have row-const chain extras"
    block = cg.config.block
    model = region_hbm_bytes_per_block(cg.plan, cg.region_plan, block)
    streamed_fallback = model + sum(
        block * c * np.dtype(g.nodes[nid].dtype).itemsize
        for nid, c in rows)
    assert model < streamed_fallback


# -- column tiling -----------------------------------------------------------

def test_kernel_col_tiling_parity_nonmultiple_width():
    """Hand-built spec, W=80 tiled at bn=32 (ragged last tile of 16): the
    tiled evaluation is allclose to the untiled kernel and to numpy."""
    k = jax.random.PRNGKey(3)
    x = jax.random.uniform(k, (24, 4), jnp.float32, -1, 1)
    w1 = jax.random.normal(jax.random.PRNGKey(4), (4, 80), jnp.float32)
    b1 = jax.random.normal(jax.random.PRNGKey(5), (80,), jnp.float32)
    w2 = jax.random.normal(jax.random.PRNGKey(6), (80, 8), jnp.float32)
    b2 = jax.random.normal(jax.random.PRNGKey(7), (8,), jnp.float32)
    steps = (("mm", 1, 0, 10, 11, 30.0, True),    # [24,80] sin layer
             ("mm", 2, 1, 12, 13, 1.0, False))    # reducer: contracts 80
    base = dict(steps=steps, stream_inputs=(0,),
                residents=(10, 11, 12, 13), outputs=(2,))
    untiled = RegionKernelSpec(**base)
    tiled = RegionKernelSpec(
        **base, tile_groups=(TileGroup(members=(1,), reducer=2,
                                       width=80, bn=32),))
    args = ([x], [], [w1, b1, w2, b2], [(8, jnp.float32)])
    out_u, = region_call(untiled, *args, bm=16, interpret=True)
    out_t, = region_call(tiled, *args, bm=16, interpret=True)
    want = np.sin(30.0 * (np.asarray(x) @ np.asarray(w1)
                          + np.asarray(b1))) @ np.asarray(w2) \
        + np.asarray(b2)
    np.testing.assert_allclose(np.asarray(out_u), want, rtol=1e-5,
                               atol=1e-5)
    # the tiled reducer reassociates its K sum: parity is allclose-only,
    # with the absolute floor scaled to the output's magnitude
    scale = float(np.max(np.abs(np.asarray(out_u))))
    np.testing.assert_allclose(np.asarray(out_t), np.asarray(out_u),
                               rtol=1e-5, atol=1e-5 * scale)


def test_scheduler_tiles_wide_region_under_tight_budget(wide_siren):
    """A budget between the tiled and untiled estimates forces the
    scheduler to column-tile instead of cutting; serving stays allclose to
    the reference executor (the reducer's K sum is reordered)."""
    cfg, _, f, x = wide_siren
    conf = FUSED.replace(bn=32, vmem_budget=120_000)
    cg = P.compile_gradient(f, 2, x, config=conf)
    tiled = [r for r in cg.region_plan.fused_regions() if r.col_tiles > 1]
    assert tiled, "the tight budget must engage column tiling, not cuts"
    assert all(r.col_tiles == 3 for r in tiled)       # ceil(80/32), ragged
    want = ex.reference_executor(cg.graph)(x)
    for a, b in zip(want, cg.apply_batched(x)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_roomy_budget_never_tiles(wide_siren):
    """Tiling trades bit-exactness for VMEM: with the default budget the
    scheduler must leave every region untiled even when runs are tilable."""
    cfg, _, f, x = wide_siren
    conf = FUSED.replace(bn=32).resolved()
    plan = build_segment_plan(_graph(cfg, f, x, 2), config=conf)
    rplan = build_region_plan(plan, conf)
    assert all(r.col_tiles == 1 for r in rplan.regions)
    # ...even though tilable runs exist in the fused regions
    any_tilable = False
    for r in rplan.fused_regions():
        members = [(plan.segments[s],
                    _lower_segment(plan, plan.segments[s]))
                   for s in r.segments]
        any_tilable |= bool(plan_col_tiles(plan, _region_io(plan, members),
                                           conf))
    assert any_tilable


# -- K-stacked resident double buffering -------------------------------------

def test_stacked_double_buffer_parity(small_siren):
    """``resident_double_buffer=True`` serves through the (K, row-tile)
    stacked megakernel grid bit-identically to the vmap path, on a
    non-block-multiple row count."""
    from repro.serve import MultiINRArtifact, bind_weights

    cfg, params, f, x = small_siren
    K = 4
    plist = [siren_init(cfg, jax.random.PRNGKey(100 + k)) for k in range(K)]
    base = P.compile_gradient(siren_fn(cfg, plist[0]), 2, x, config=FUSED)
    payloads = [bind_weights(base, plist[0], p) for p in plist]
    vmapped = MultiINRArtifact(base, payloads)
    stacked = MultiINRArtifact(base, payloads, resident_double_buffer=True)
    assert not vmapped.double_buffered
    assert stacked.double_buffered
    q = jax.random.uniform(jax.random.PRNGKey(9),
                           (19, cfg.in_features), jnp.float32, -1, 1)
    for a, b in zip(vmapped.apply_batched(q), stacked.apply_batched(q)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stacked_falls_back_when_not_applicable(small_siren):
    """Interpreted pipelines can't take the stacked Pallas path: the flag
    downgrades to the vmap path instead of failing."""
    from repro.serve import MultiINRArtifact, bind_weights

    cfg, params, f, x = small_siren
    base = P.compile_gradient(f, 1, x, config=INTERP_UNFUSED)
    payloads = [bind_weights(base, params, params)]
    m = MultiINRArtifact(base, payloads, resident_double_buffer=True)
    assert not m.double_buffered
    outs = m.apply_batched(x[:5])
    assert all(np.all(np.isfinite(o)) for o in outs)


def test_region_call_stacked_matches_per_lane_calls():
    """Kernel-level: one stacked (K, row-tile) grid == K separate
    region_call invocations, bit-for-bit, including bcast_rows."""
    K, R = 3, 20
    x = jax.random.uniform(jax.random.PRNGKey(0), (K, R, 4), jnp.float32)
    row = jax.random.normal(jax.random.PRNGKey(1), (K, 1, 16), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (K, 4, 16), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(3), (K, 16), jnp.float32)
    steps = (("mm", 2, 0, 10, 11, 1.0, False),
             ("chain", 3, 2, (("mul", None),), (1,)))
    spec = RegionKernelSpec(steps=steps, stream_inputs=(0,),
                            residents=(10, 11), outputs=(3,),
                            bcast_rows=(1,))
    out_info = [(16, jnp.float32)]
    got, = region_call_stacked(spec, [x], [row], [w, b], out_info, bm=8,
                               interpret=True)
    assert got.shape == (K, R, 16)
    for k in range(K):
        want, = region_call(spec, [x[k]], [row[k]], [w[k], b[k]], out_info,
                            bm=8, interpret=True)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got[k]))


# -- calibrated row costs ----------------------------------------------------

def test_load_op_row_cost_roundtrip(tmp_path):
    from repro.core import dataflow

    p = tmp_path / "costs.json"
    p.write_text(json.dumps({"op_row_cost": {"Sin": 7, "Nope": 0.2},
                             "mm_row_cost_per_k": 0.5}))
    try:
        loaded = dataflow.load_op_row_cost(p)
        assert dataflow.OP_ROW_COST["Sin"] == 7
        assert dataflow.OP_ROW_COST["Nope"] == 1       # clamped to >= 1
        assert dataflow.MM_ROW_COST_PER_K == 0.5
        assert loaded["Sin"] == 7
    finally:
        dataflow.reset_op_row_cost()
    assert dataflow.OP_ROW_COST == dataflow._ANALYTIC_OP_ROW_COST
    assert dataflow.MM_ROW_COST_PER_K == 1.0


def test_committed_calibration_loads(small_siren):
    """The checked-in ``results/op_row_cost.json`` loads, changes MM row
    costs, and the oracle still prices a plan under it."""
    from pathlib import Path

    from repro.core import dataflow
    from repro.core.dataflow import map_to_dataflow

    path = Path(__file__).resolve().parents[1] / "results" \
        / "op_row_cost.json"
    assert path.exists()
    cfg, _, f, x = small_siren
    g = _graph(cfg, f, x, 1)
    try:
        loaded = dataflow.load_op_row_cost(path)
        assert loaded and all(v >= 1 for v in loaded.values())
        d = map_to_dataflow(g, config=FUSED.resolved())
        assert d.processes
    finally:
        dataflow.reset_op_row_cost()


# -- chunk_blocks in the autoconfig search -----------------------------------

def test_autoconfig_chunk_blocks_deterministic(small_siren):
    """Same graph + same measure hook -> byte-identical config, twice."""
    from repro.core import autoconfig as AC

    cfg, _, f, x = small_siren
    g = _graph(cfg, f, x, 1)
    measure = lambda c: float(c.chunk_blocks + c.bm + c.bn)  # noqa: E731
    a = AC.resolve_config(g, measure=measure)
    b = AC.resolve_config(g, measure=measure)
    assert a.config == b.config
    assert a.config.chunk_blocks == min(AC.CHUNK_LADDER)


def test_autoconfig_measure_ranks_chunk_blocks(small_siren):
    """A measure hook preferring LARGE serving chunks steers chunk_blocks to
    the top of the ladder without touching the analytic winner's tiles."""
    from repro.core import autoconfig as AC

    cfg, _, f, x = small_siren
    g = _graph(cfg, f, x, 1)
    res = AC.resolve_config(g, measure=lambda c: -float(c.chunk_blocks))
    assert res.config.chunk_blocks == max(AC.CHUNK_LADDER)
