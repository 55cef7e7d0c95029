"""Builds a checkout for the benchmark's CPU rehearsals: a copy of
``bench/`` beside the program's ``src/``, with tiny cells added as files
of their own (a configuration, traffic mixes, limits) and a
``BENCHMARK.json`` that names them.  Nothing of the committed benchmark is
edited: a tiny cell differs only in the files it adds.

Besides tiny SIREN cells, the checkout holds a second architecture, a
tanh MLP, brought in as a new architecture comes: its configurations,
its reference (``tanh_mlp/reference.py``) and its program hook
(``tanh_mlp/program.py``) as files of their own."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

REPO = Path(__file__).resolve().parents[2]

TINY = {
    "name": "siren-tiny", "source": "https://arxiv.org/abs/2006.09661",
    "in_features": 2, "out_features": 1, "hidden_features": 16,
    "num_hidden_layers": 3, "first_omega_0": 30.0, "hidden_omega_0": 30.0,
    "outermost_linear": True, "dtype": "float32", "precision": "highest",
    "architecture": "siren", "reference": "siren",
    "reduced": ["hidden_features"],
    "flops_per_row": {"tower_o1": 1.0, "tower_o2": 1.0, "tower_o3": 1.0,
                      "grad_mse_vg_o1": 1.0},
}

# a second architecture: 2 -> 3 x 16 tanh -> 1 and 3 -> 3 x 16 tanh -> 1;
# the counts are those of bench/flops.py, which a test recomputes
TANH = {
    "name": "tanh-tiny", "source": "https://arxiv.org/abs/1711.10561",
    "in_features": 2, "out_features": 1, "hidden_features": 16,
    "hidden_layers": 3, "dtype": "float32", "precision": "highest",
    "architecture": "tanh_mlp", "reference": "tanh_mlp", "reduced": [],
    "flops_per_row": {"tower_o2": 8177.0, "grad_mse_vg_o1": 7521.75},
}
TANH3 = dict(TANH, name="tanh-tiny-3d", in_features=3,
             flops_per_row={"grad_mse_vg_o1": 7687.0})
ARCHITECTURES = {"tanh_mlp": REPO / "tests" / "bench" / "tanh_mlp"}

# tiny traffic: same generators and keys as the committed mixes, small sizes
TRAFFIC = {
    "tiny-bank": {"generator": "closed_serve", "order": 2,
                  "filters": ["identity", "blur", "edge", "laplacian",
                              "sharpen"],
                  "alpha": 0.15, "grid_side": 32, "tile_side": 16,
                  "trace_rows": 16, "warmup_calls": 1, "trace_at": 0.3,
                  "trace_slice_s": 0.5, "work": "tower_o2", "row_cols": 7,
                  "weight_passes": 1},
    "tiny-o3": {"generator": "closed_serve", "order": 3, "grid_side": 16,
                "tile_side": 8, "trace_rows": 16, "warmup_calls": 1,
                "trace_at": 0.3, "trace_slice_s": 0.5, "work": "tower_o3",
                "row_cols": 17, "weight_passes": 1},
    "tiny-fit": {"generator": "fit_job", "order": 1, "loss": "grad_mse",
                 "points": 512, "batch_rows": 128, "piece_rows": 64,
                 "spheres": {"count": 2, "centre": [-0.5, 0.5],
                             "radius": [0.2, 0.4]},
                 "adam": {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
                          "constant_rate_steps": 1000000000},
                 "reference_steps": 3, "curve_steps": 8, "trace_rows": 16,
                 "trace_steps": 2, "trace_lead_s": 0.2,
                 "work": "grad_mse_vg_o1", "row_cols": 6,
                 "weight_passes": 2},
}
TRAFFIC["tiny-tanh-o2"] = dict(TRAFFIC["tiny-o3"], order=2, work="tower_o2",
                               row_cols=7)
TRAFFIC["tiny-tanh-fit"] = dict(TRAFFIC["tiny-fit"])

CELLS = {"tiny-bank": ("siren-tiny", "tiny-bank"),
         "tiny-o3": ("siren-tiny", "tiny-o3"),
         "tiny-fit": ("siren-tiny-3d", "tiny-fit"),
         "tiny-tanh-o2": ("tanh-tiny", "tiny-tanh-o2"),
         "tiny-tanh-fit": ("tanh-tiny-3d", "tiny-tanh-fit")}

# each tiny cell is held to the limits of the committed cell it stands for
LIMITS_OF = {"tiny-bank": "image-edit-bank", "tiny-o3": "image-grad-o3",
             "tiny-fit": "sdf-fit-normals", "tiny-tanh-o2": "image-edit-bank",
             "tiny-tanh-fit": "sdf-fit-normals"}

CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
             "hbm_bytes": 1e10}


def _dump(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=2) + "\n")


def make_checkout(tmp: Path) -> Path:
    """A checkout under ``tmp`` whose BENCHMARK.json holds the committed
    cells plus the tiny ones, each tiny one in files of its own."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for arch, src in ARCHITECTURES.items():
        shutil.copy(src / "reference.py",
                    root / "bench" / "reference" / f"{arch}.py")
        shutil.copy(src / "program.py",
                    root / "bench" / "programs" / f"{arch}.py")
    tiny3 = dict(TINY, name="siren-tiny-3d", in_features=3)
    for cfg in (TINY, tiny3, TANH, TANH3):
        _dump(root / "bench" / "configs" / f"{cfg['name']}.json", cfg)
        spec["configs"].append({"name": cfg["name"], "source": cfg["source"],
                                "file": f"bench/configs/{cfg['name']}.json",
                                "reduced": cfg["reduced"],
                                "why": "CPU rehearsal at tiny width"})
    for name, tr in TRAFFIC.items():
        _dump(root / "bench" / "traffic" / f"{name}.json", tr)
    for cell, (config, traffic) in CELLS.items():
        shutil.copy(REPO / "bench" / "limits" / f"{LIMITS_OF[cell]}.json",
                    root / "bench" / "limits" / f"{cell}.json")
        spec["workloads"].append({"name": cell, "config": config,
                                  "traffic": traffic, "chips": 1,
                                  "why": "CPU rehearsal at tiny width"})
    # a tiny cell reports whatever the committed cell it stands for does
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [cell for cell, of in LIMITS_OF.items()
                               if of in m["workloads"]]
    _dump(root / "BENCHMARK.json", spec)
    return root


def load_run(root: Path):
    """The checkout's ``bench/run.py`` as a module."""
    import importlib.util
    for name in [m for m in sys.modules
                 if m in ("harness", "program", "serving", "work",
                          "trace_reduce", "step_window")]:
        del sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        "bench_run_rehearsal", root / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rehearse(root: Path, workload: str, *, seed: int = 3, seconds=1.0,
             trace: int = 0, control=None):
    """One run of ``workload`` on the CPU (the look for a chip skipped);
    returns the result line.  ``control`` runs the generator's control too;
    its readings come back under ``"control"``."""
    run = load_run(root)
    args = SimpleNamespace(workload=workload, seed=seed, seconds=seconds,
                           trace=trace, trace_record=None)
    return run.execute(args, root=root, chip=False, peaks=CPU_PEAKS,
                       control=control)
