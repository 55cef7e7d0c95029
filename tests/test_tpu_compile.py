"""Ahead-of-time compiles of the main path's kernels for a described TPU v5e.

Nothing here runs on a chip: each test lowers one jitted program of the
seed SIREN (2 -> 256 x 3 -> 1) with Pallas dispatch on and interpret mode
off, compiles it for one chip of a described ``v5e:2x2`` topology, and
checks that the compiler kept a Mosaic kernel (``tpu_custom_call``).  This
catches what interpret mode cannot: block shapes Mosaic refuses, kernels
that overflow VMEM, programs the TPU compiler rejects.

The topology is described inside a fixture (never at import), so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.siren import SirenConfig
from repro.core import pipeline as P
from repro.core.config import HardwareConfig
from repro.fit import ValueMSE, compile_fit
from repro.inr.siren import siren_fn, siren_init

HW = HardwareConfig(use_pallas=True)
TRACE_ROWS = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def seed_siren():
    cfg = SirenConfig()
    params = siren_init(cfg, jax.random.PRNGKey(0))
    return cfg, params, siren_fn(cfg, params)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels lower for Mosaic (interpret mode off in every kernel module
    on the path) and the persistent cache is off: a compile for a described
    chip cannot be read back here."""
    import repro.kernels.fused_chain as fused_chain
    import repro.kernels.region as region
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(region, "interpret_default", lambda: False)
    monkeypatch.setattr(fused_chain, "interpret_default", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _shape(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("order", [1, 2])
def test_serving_chunk_step_compiles(order, seed_siren, one_chip,
                                     compiled_kernels):
    cfg, _, f = seed_siren
    cg = P.compile_gradient(f, order, jnp.zeros((TRACE_ROWS, 2)), config=HW)
    assert cg.config.use_pallas
    chunk = _shape((cg.config.chunk_blocks, cg.config.block,
                    cfg.in_features), one_chip)
    text = jax.jit(cg._make_chunk_fn()).lower(chunk).compile().as_text()
    assert "tpu_custom_call" in text
    assert "%region_fwd." in text, "the kernel's name is its instruction's"


def test_order3_request_map_compiles(seed_siren, one_chip, compiled_kernels):
    """``apply_batched`` serves a 128 x 128 tile at order 3 as one map
    over its 2,048 blocks: that long map lowers for the chip, with its
    region and standalone chain kernels."""
    cfg, _, f = seed_siren
    cg = P.compile_gradient(f, 3, jnp.zeros((TRACE_ROWS, 2)), config=HW)
    request = _shape((2048, cg.config.block, cfg.in_features), one_chip)
    text = jax.jit(cg._make_chunk_fn()).lower(request).compile().as_text()
    assert "%region_fwd." in text and "%fused_chain." in text


def test_fit_stream_value_and_grad_compiles(seed_siren, one_chip,
                                            compiled_kernels):
    """Order-1 streamed fit step: forward region kernel plus the
    accumulating region backward kernel."""
    cfg, params, f = seed_siren
    cf = compile_fit(f, ValueMSE(), 1, jnp.zeros((TRACE_ROWS, 2)),
                     params=params, config=HW)
    assert any(kind == "region" for kind, _ in cf.cg.region_plan.units())
    leaves = tuple(_shape(l.shape, one_chip)
                   for l in jax.tree_util.tree_leaves(params))
    rows = 8 * cf.config.block
    coords = _shape((rows, cfg.in_features), one_chip)
    targets = _shape((rows, cfg.out_features), one_chip)
    text = jax.jit(cf._stream_vg).lower(leaves, coords,
                                        targets).compile().as_text()
    assert text.count("tpu_custom_call") >= 2     # forward + backward
    # under autodiff the names read jvp_region_fwd_ and
    # transpose_jvp_region_bwd__
    assert "region_fwd" in text and "region_bwd" in text


def test_order3_standalone_fused_chain_compiles(seed_siren, one_chip,
                                                compiled_kernels):
    """Order 3 leaves standalone ``fused_chain`` segments between its
    regions; one of them, at the block shape it is served at."""
    from repro.kernels.fused_chain import fused_chain
    _, _, f = seed_siren
    cg = P.compile_gradient(f, 3, jnp.zeros((TRACE_ROWS, 2)), config=HW)
    chains = [s for s in cg.plan.segments
              if cg._decisions[s.id] == "fused_chain"]
    assert chains, "order 3 at seed width dispatches standalone chains"
    spec = chains[0].meta["chain"]
    cols = cg.graph.nodes[spec.x].shape[-1]
    x = _shape((cg.config.block, cols), one_chip)
    extras = [_shape((cg.config.block, cols), one_chip) for _ in spec.extras]

    def call(x, *extras):
        return fused_chain(x, spec.steps, tuple(extras),
                           block_rows=cg.config.bm)
    text = jax.jit(call).lower(x, *extras).compile().as_text()
    assert "tpu_custom_call" in text and "%fused_chain." in text


def test_igr_fit_step_compiles_at_published_width(one_chip, compiled_kernels):
    """IGR's 8 x 512 Softplus network, order-1 fit under ``IGRLoss``: the
    compare, select and log1p steps lower for Mosaic, and the 512-wide
    regions' backward kernels (a layer's weight, its transpose and their
    gradient accumulators, double-buffered) get the scoped VMEM they need
    (``kernels.common.vmem_params``) instead of overflowing 16 MiB."""
    from repro.fit import IGRLoss
    from repro.inr.igr import IGRConfig, igr_fn, igr_init
    cfg = IGRConfig()
    params = igr_init(cfg, jax.random.PRNGKey(0))
    cf = compile_fit(igr_fn(cfg, params), IGRLoss(), 1,
                     jnp.zeros((TRACE_ROWS, 3)), params=params, config=HW)
    units = cf.cg.region_plan.fit_units()
    assert all(kind == "region" for kind, _ in units)
    leaves = tuple(_shape(l.shape, one_chip)
                   for l in jax.tree_util.tree_leaves(params))
    rows = 8 * cf.config.block
    coords = _shape((rows, 3), one_chip)
    text = jax.jit(cf._stream_vg).lower(leaves, coords,
                                        coords).compile().as_text()
    assert text.count("tpu_custom_call") == 2 * len(units)


def test_bank_region_with_head_slices_compiles(seed_siren, one_chip,
                                               compiled_kernels):
    """The five-filter order-2 bank: its heads' column slices are region
    steps, so one region megakernel holds the tower and the heads; that
    region, with its lane-offset slices, lowers for Mosaic."""
    from repro.inr.filters import filter_bank
    _, _, f = seed_siren
    bank = filter_bank(f, ["identity", "blur", "edge", "laplacian",
                           "sharpen"], jnp.zeros((TRACE_ROWS, 2)),
                       alpha=0.15, config=HW)
    cg = bank.cg
    assert any(s[0] == "slice" for r in cg.region_plan.fused_regions()
               for s in r.spec.steps)
    request = _shape((2048, cg.config.block, 2), one_chip)
    text = jax.jit(cg._make_chunk_fn()).lower(request).compile().as_text()
    assert "%region_fwd." in text
