"""The operations per row recorded in each configuration file match a
fresh XLA cost analysis of the plain reference (CPU backend), for the
committed configurations and the rehearsal's second architecture, and
every cell's ``work`` is among its configuration's recorded counts."""

from __future__ import annotations

import json
import sys

import pytest

import benchtools as bt

sys.path.insert(0, str(bt.REPO / "bench"))
import flops  # noqa: E402
import harness  # noqa: E402

SPEC = json.loads((bt.REPO / "BENCHMARK.json").read_text())
BENCH = bt.REPO / "bench"


def _configs() -> dict:
    """(configuration, its reference file, the work its cells name) by
    file for the committed ones and by name for the rehearsal's tanh MLP."""
    out = {}
    for c in SPEC["configs"]:
        cfg = json.loads((bt.REPO / c["file"]).read_text())
        work = {json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                           .read_text())["work"]
                for w in SPEC["workloads"] if w["config"] == c["name"]}
        out[c["file"]] = (cfg, BENCH / "reference" / f"{cfg['reference']}.py",
                          work)
    for cfg in (bt.TANH, bt.TANH3):
        work = {bt.TRAFFIC[t]["work"] for c, t in bt.CELLS.values()
                if c == cfg["name"]}
        out[cfg["name"]] = (cfg, bt.ARCHITECTURES[cfg["reference"]]
                            / "reference.py", work)
    return out


CONFIGS = _configs()


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_recorded_flops_match_cost_analysis(config):
    cfg, reference, _ = CONFIGS[config]
    ref = harness.load_module(reference, "flops_reference")
    fresh = flops.flops_per_row(cfg, ref)
    for k, v in fresh.items():
        assert cfg["flops_per_row"][k] == pytest.approx(v, rel=1e-9), k


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_cells_work_is_recorded(config):
    cfg, _, work = CONFIGS[config]
    assert work and work <= set(cfg["flops_per_row"])


def test_unknown_count_is_refused():
    cfg, reference, _ = CONFIGS["tanh-tiny"]
    ref = harness.load_module(reference, "flops_reference")
    with pytest.raises(ValueError, match="tower_x2"):
        flops.flops_per_row(dict(cfg, flops_per_row={"tower_x2": 1.0}), ref)
