"""The benchmark's harness without a chip: it refuses to run on the CPU
and in a directory with nothing but the benchmark, its BENCHMARK.json
keeps to its contract, and a cell whose configuration, traffic, limits
and per-layer metric are files of their own runs with no edit to any
existing file."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path


import numpy as np
import pytest

import benchtools as bt

REPO = bt.REPO
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _run(cwd: Path, *args, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    e.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=e, capture_output=True, text=True, timeout=300)


def test_cpu_backend_is_refused():
    r = _run(REPO, "--workload", "image-edit-bank", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert "{" not in r.stdout


def test_bare_benchmark_directory_is_refused(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, "--workload", "image-grad-o3", "--seed", "2",
             "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert "{" not in r.stdout


@pytest.mark.parametrize("architecture", [None, "no-such-network"])
def test_configuration_without_its_program_is_refused(tmp_path,
                                                      architecture):
    """A configuration that names no architecture, or one with no
    ``programs/`` file, is refused before set-up: no result line."""
    root = bt.make_checkout(tmp_path)
    path = root / "bench" / "configs" / "siren-tiny.json"
    cfg = json.loads(path.read_text())
    cfg.pop("architecture")
    if architecture:
        cfg["architecture"] = architecture
    path.write_text(json.dumps(cfg))
    r = _run(root, "--workload", "tiny-o3", "--seed", "4", "--seconds", "1",
             "--trace", "0")
    assert r.returncode != 0
    assert "architecture" in r.stderr and "needs a TPU" not in r.stderr
    assert "{" not in r.stdout


def _program():
    sys.path.insert(0, str(REPO / "bench"))
    import harness
    import program
    return harness, program


@pytest.mark.parametrize("config", [c["file"] for c in SPEC["configs"]])
def test_program_inr_is_the_siren_function(config):
    """On a committed configuration the hook gives, bit for bit, the
    program's ``siren_fn`` under the configuration's ``SirenConfig``."""
    import jax
    from repro.configs.siren import SirenConfig
    from repro.inr.siren import siren_fn
    harness, program = _program()
    cfg = json.loads((REPO / config).read_text())
    ref = harness.load_module(REPO / "bench" / "reference" / "siren.py",
                              "hook_reference")
    params = ref.init_params(cfg, jax.random.PRNGKey(5))
    x = jax.random.uniform(jax.random.PRNGKey(6), (64, cfg["in_features"]),
                           minval=-1.0, maxval=1.0)
    want = siren_fn(SirenConfig(
        in_features=cfg["in_features"], out_features=cfg["out_features"],
        hidden_features=cfg["hidden_features"],
        hidden_layers=cfg["num_hidden_layers"] + 1,
        w0=cfg["hidden_omega_0"]), params)(x)
    np.testing.assert_array_equal(program.inr(cfg, params)(x), want)


@pytest.mark.parametrize("architecture", ["siren", "tanh_mlp"])
@pytest.mark.parametrize("change", [{"precision": "high"},
                                    {"dtype": "bfloat16"}])
def test_program_inr_refuses_other_precision(architecture, change):
    _, program = _program()
    cfg = dict(bt.TINY if architecture == "siren" else bt.TANH, **change)
    with pytest.raises(ValueError, match="float32 at highest"):
        program.inr(cfg, params=None)


def test_spec_keeps_to_its_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert all((REPO / p).is_dir() for p in SPEC["paths"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    configs = {c["name"]: c for c in SPEC["configs"]}
    bench = REPO / SPEC["paths"][0]
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert (bench / "programs" / f"{cfg['architecture']}.py").is_file()
        assert (bench / "reference" / f"{cfg['reference']}.py").is_file()
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in SPEC["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        traffic = json.loads((bench / "traffic" /
                              f"{w['traffic']}.json").read_text())
        assert (bench / "generators" / f"{traffic['generator']}.py").is_file()
        assert (bench / "limits" / f"{w['name']}.json").is_file()
        reported = [m for m in SPEC["end_to_end"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert len(reported) >= 2
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert (bench / "layer_metrics" / f"{m['name']}.py").is_file()
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])


def test_cell_from_files_of_its_own(tmp_path):
    """A configuration, a traffic mix, a limits file and a per-layer
    metric added as new files plus new entries: the harness finds them all
    by name."""
    root = bt.make_checkout(tmp_path)
    metric = root / "bench" / "layer_metrics" / "fixture.calls.rows.py"
    metric.write_text("def read(ctx):\n"
                      "    return ctx.work.get('calls') or None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({"name": "fixture.calls.rows", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "engines", "moves": "rows_per_s",
                              "workloads": ["tiny-o3"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    line = bt.rehearse(root, "tiny-o3", trace=1, seconds=2.0)
    assert line["correct"], line["checks"]
    assert line["metrics"]["fixture.calls.rows"]["value"] >= 1
    assert "window_s" in line["device"] and "breakdown" in line
    assert list(line)[-1] == "checks"


def test_control_lines_judge_each_variant():
    """Each control or planted fault goes through ``result_line`` against
    the cell's own limits: one number over its limit, or one that is not a
    number, makes that variant not correct and leaves the others be."""
    sys.path.insert(0, str(REPO / "bench"))
    import harness
    from types import SimpleNamespace
    cell = harness.load_cell("sdf-fit-normals", REPO)
    sound = {n: 0.0 for n in cell.limits}
    over = dict(sound, grad1_gap=10 * cell.limits["grad1_gap"])
    nan = dict(sound, curve_head_gap=float("nan"))
    outcome = harness.Outcome(
        attempted=1, failed=0,
        values={"fit_rows_per_s": 1.0, "peak_hbm_mb": 1.0},
        checks=harness.checks_of(sound, cell.limits),
        controls={"sound": sound, "over": over, "nan": nan})
    run = SimpleNamespace(trace=False, setup_s=1.0, memory_peak_bytes=None)
    line = harness.result_line(cell, run, outcome, peaks={})
    assert line["correct"] and list(line)[-1] == "checks"
    got = harness.control_lines(cell, run, outcome, peaks={})
    assert {v: g["correct"] for v, g in got.items()} == {
        "sound": True, "over": False, "nan": False}
    assert set(got["over"]["checks"]) == set(cell.limits)
