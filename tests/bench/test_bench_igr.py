"""The IGR fit generator (``generators/igr_fit.py``) rehearsed on the CPU at
tiny width through the harness, with the look for a chip skipped: a tiny
IGR configuration (3 -> 8 x 16 Softplus -> 1, skip at layer 4) and traffic
mix are added to a rehearsal checkout as files of their own, the way the
committed ``igr-sdf`` / ``igr-fit-eikonal`` cell came in.  A sound run
comes out correct; the planted faults and the control do not."""

from __future__ import annotations

import json
import shutil

import jax.numpy as jnp
import pytest

import benchtools as bt
from test_bench_cells import bf16x3_dot

IGR_TINY = {
    "name": "igr-tiny", "source": "https://arxiv.org/abs/2002.10099",
    "in_features": 3, "out_features": 1, "dims": [16] * 8, "skip_in": [4],
    "geometric_init": True, "radius_init": 1, "beta": 100,
    "points_batch": 64, "global_samples": 8, "global_sigma": 1.8,
    "local_sigma_neighbours": 50, "normals_lambda": 1, "grad_lambda": 0.1,
    "dtype": "float32", "precision": "highest", "architecture": "igr",
    "reference": "igr", "reduced": ["dims"],
    "flops_per_row": {"igr_loss_vg_o1": 1.0},
}
# 256 surface rows, as many local samples and 32 global ones: 68 blocks
# of 8 rows, 17 a step, 4 steps an epoch
TRAFFIC = dict(json.loads((bt.REPO / "bench" / "traffic" /
                           "igr-eikonal16k.json").read_text()),
               surface_points=256, global_points=32, batch_rows=136,
               piece_rows=64, curve_steps=5, trace_rows=16, trace_steps=2)
CELL = "tiny-igr-fit"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = bt.make_checkout(tmp_path_factory.mktemp("bench"))
    bench = root / "bench"
    (bench / "configs" / "igr-tiny.json").write_text(json.dumps(IGR_TINY))
    (bench / "traffic" / "tiny-igr.json").write_text(json.dumps(TRAFFIC))
    shutil.copy(bench / "limits" / "igr-fit-eikonal.json",
                bench / "limits" / f"{CELL}.json")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "igr-tiny", "source": IGR_TINY["source"],
                            "file": "bench/configs/igr-tiny.json",
                            "reduced": ["dims"],
                            "why": "CPU rehearsal at tiny width"})
    spec["workloads"].append({"name": CELL, "config": "igr-tiny",
                              "traffic": "tiny-igr", "chips": 1,
                              "why": "CPU rehearsal at tiny width"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "igr-fit-eikonal" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_igr_cell_runs_correct(root):
    line = bt.rehearse(root, CELL)
    assert line["correct"], line["checks"]
    assert line["attempted"] == 1 and line["failed"] == 0
    assert set(line["checks"]) == {"grad1_gap", "steps_gap",
                                   "curve_head_gap"}
    assert line["metrics"]["fit_rows_per_s"]["value"] > 0


def _frozen(monkeypatch):
    """Every fit step leaves the parameters and state unchanged."""
    from repro.fit import engine

    def frozen(cfg, params, grads, opt, step):
        return params, opt, jnp.zeros(())
    monkeypatch.setattr(engine, "adamw_update", frozen)


def _half(monkeypatch):
    """Each step's loss and gradient taken over half its rows."""
    from repro.fit import engine
    whole = engine._chunk_vg

    def half(cf, leaves, xc, yc, mc, n_rows):
        h = xc.shape[0] // 2
        return whole(cf, leaves, xc[:h], yc[:h], mc[:h], jnp.sum(mc[:h]))
    monkeypatch.setattr(engine, "_chunk_vg", half)


@pytest.mark.parametrize("fault", [_frozen, _half],
                         ids=["unchanged_state", "half_batch"])
def test_igr_planted_fault_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    line = bt.rehearse(root, CELL)
    assert not line["correct"], line["checks"]


def test_igr_control_separates(root):
    """The reference in the program's place, one precision step down, and
    with each fault planted in it: none comes out correct."""
    line = bt.rehearse(root, CELL, control={"precision": "high",
                                            "dot": bf16x3_dot})
    assert line["correct"], line["checks"]
    got = {v: g["correct"] for v, g in line["controls"].items()}
    assert got == {"control": False, "half_batch": False,
                   "unchanged_state": False}, line["controls"]


def test_igr_traced_run_reads_the_compile_counters(root):
    """A traced run reports the nodes ``compile_fit`` left to the
    interpreter, read from the program's counter at set-up: on the CPU the
    fit is interpreted, so every node of its graph counts."""
    line = bt.rehearse(root, CELL, trace=1, seconds=2.0)
    assert line["correct"], line["checks"]
    nodes = line["metrics"]["compile.interp_nodes.fit"]
    assert nodes["unit"] == "nodes" and nodes["value"] > 0
    assert "mfu.fit" in line["metrics"], line["metrics"]


def test_program_inr_is_the_igr_function():
    """On the committed ``igr-sdf`` configuration the hook gives, bit for
    bit, the program's ``igr_fn`` under the configuration's IGRConfig,
    with the reference's geometric initialisation as the weights."""
    import sys
    import jax
    import numpy as np
    from repro.inr.igr import IGRConfig, igr_fn
    sys.path.insert(0, str(bt.REPO / "bench"))
    import harness
    import program
    cfg = json.loads((bt.REPO / "bench" / "configs" / "igr-sdf.json")
                     .read_text())
    ref = harness.load_module(bt.REPO / "bench" / "reference" / "igr.py",
                              "igr_hook_reference")
    params = ref.init_params(cfg, jax.random.PRNGKey(5))
    x = jax.random.uniform(jax.random.PRNGKey(6), (64, 3), minval=-1.0,
                           maxval=1.0)
    want = igr_fn(IGRConfig(in_features=3, out_features=1,
                            hidden_features=512, hidden_layers=8,
                            skip_in=(4,), beta=100.0, radius_init=1.0),
                  params)(x)
    np.testing.assert_array_equal(program.inr(cfg, params)(x), want)
    # the reference's own forward agrees to rounding (its softplus is
    # jnp.logaddexp; scaled error, a few ulps of f ~ 1)
    got = ref.apply(cfg, params, x)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5
