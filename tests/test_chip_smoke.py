"""chip_smoke.py rehearsed on the CPU: its phases at a tiny width with the
Pallas kernels in interpret mode, its four-device phase on four virtual CPU
devices, and its refusal to run anything without a TPU."""

from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys

from tests.conftest import run_with_devices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    r = _run(SCRIPT, REPO)
    assert r.returncode != 0
    assert r.stdout == "", r.stdout
    assert "needs a TPU" in r.stderr


def test_refuses_outside_a_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    r = _run(str(lone), str(tmp_path))
    assert r.returncode != 0
    assert r.stdout == "", r.stdout
    assert "no src/repro" in r.stderr


def test_single_chip_phases_tiny_width():
    """Every one-chip phase end to end (serve orders 1-3 sync and async,
    restore, bank, fit, auto) at hidden width 16 over a 24 x 24 grid, which
    still spans one full serving chunk plus remainder blocks."""
    from repro.configs.siren import SirenConfig
    from repro.core.config import HardwareConfig

    cs = _load()
    failures = cs.run_single_chip(
        seed=0, siren=SirenConfig(hidden_features=16, hidden_layers=1),
        grid_side=24, ragged=(13, 37), fit_steps=3,
        hw=HardwareConfig(use_pallas=True))
    assert failures == []


def test_fleet_phase_on_four_devices():
    """The ``--chips 4`` phase: K = 8 lanes sharded four ways run under
    shard_map with no all-gather, matching one device."""
    out = run_with_devices(f"""
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
from repro.configs.siren import SirenConfig
from repro.core.config import HardwareConfig
failures = cs.run_fleet(seed=0, rows=64,
                        siren=SirenConfig(hidden_features=16,
                                          hidden_layers=1),
                        hw=HardwareConfig(use_pallas=True))
assert failures == [], failures
""", n=4)
    assert "0 all-gathers" in out
    assert "per device" in out
