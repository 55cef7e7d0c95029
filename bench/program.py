"""The program under test, as the benchmark drives it.

Only the system's public entry points are used: the INR functions, the
compile front doors (``compile_gradient``, ``filter_bank``,
``compile_fit``), the engines and ``fit``.  Weights and inputs are the
benchmark's own, made from the seed; the program is handed them.

A configuration names its network by ``architecture``; the file
``programs/<architecture>.py`` builds it from the program's public entry
points with ``inr(cfg, params) -> f``, ``f: x [B, in] -> y [B, out]``.
"""

from __future__ import annotations

from pathlib import Path

from harness import load_module

PROGRAMS = Path(__file__).resolve().parent / "programs"


def inr(cfg: dict, params):
    """The configuration's INR as the program traces it."""
    if cfg["precision"] != "highest" or cfg["dtype"] != "float32":
        raise ValueError("the program computes float32 at highest precision")
    arch = cfg["architecture"]
    return load_module(PROGRAMS / f"{arch}.py",
                       f"bench_program_{arch}").inr(cfg, params)


def release() -> None:
    """Drop the program's in-process compile caches, so that what the
    window compiled is freed before the reference runs."""
    import gc
    from repro.core import pipeline
    pipeline.clear_compile_cache()
    gc.collect()
