"""The operations per row recorded in each configuration file match a
fresh XLA cost analysis of the plain reference (CPU backend)."""

from __future__ import annotations

import json
import sys

import pytest

import benchtools as bt

sys.path.insert(0, str(bt.REPO / "bench"))
import flops  # noqa: E402
import harness  # noqa: E402

SPEC = json.loads((bt.REPO / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("config", [c["file"] for c in SPEC["configs"]])
def test_recorded_flops_match_cost_analysis(config):
    cfg = json.loads((bt.REPO / config).read_text())
    ref = harness.load_module(
        bt.REPO / "bench" / "reference" / f"{cfg['reference']}.py",
        "flops_reference")
    fresh = flops.flops_per_row(cfg, ref)
    assert set(fresh) == set(cfg["flops_per_row"])
    for k, v in fresh.items():
        assert cfg["flops_per_row"][k] == pytest.approx(v, rel=1e-9), k
