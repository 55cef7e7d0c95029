#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  The cell (an entry of ``workloads`` in
``BENCHMARK.json``) names a configuration and a traffic mix; see
``bench/harness.py`` for the files each is made of.  Set-up (JAX start,
weights and inputs from the seed, compile, warm-up of the cell's shapes)
is timed as ``setup_s``; then the window runs for ``--seconds``; then the
outputs of the window are compared with the plain reference.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
a steady slice of the window with the JAX profiler and reports the cell's
per-layer metrics, the device's busy and traced seconds, and a breakdown.
The last line of stdout is one JSON object; the numbers compared, each
with its limit, are the last lines of stderr and the last key of that
object.  Without a TPU, with Pallas in interpret mode, or with fewer chips
than the cell asks for, the run exits non-zero before any window and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import (BenchError, Run, control_lines,  # noqa: E402
                     load_cell, peaks_for, result_line)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-record", metavar="PATH",
                    help="also write the slice's extracted trace record "
                    "(its first 5,000 device operations)")
    return ap.parse_args(argv)


def require_chip(chips: int) -> None:
    """A TPU with compiled (not interpreted) Pallas kernels and at least
    ``chips`` devices, or no run at all."""
    import jax
    if jax.default_backend() != "tpu":
        raise BenchError(f"needs a TPU, JAX found {jax.default_backend()!r}")
    from repro.kernels.common import interpret_default
    if interpret_default():
        raise BenchError("Pallas would run in interpret mode")
    if jax.device_count() < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{jax.device_count()}")


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` when set,
    else at the fixed ``<checkout>/.jax_cache``; every compile is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def execute(args, *, root: Path = ROOT, chip: bool = True,
            peaks: dict | None = None, t_start: float = T_START,
            control: dict | None = None) -> dict:
    """One run; returns the result line.  ``chip=False`` skips the look
    for a chip (the CPU rehearsal in the tests), and then ``peaks`` stands
    in for the table.  ``control`` (``{"precision": ...}``) also puts the
    correctness control and the generator's planted faults in the
    program's place, each judged as a run is, under ``"controls"``; the
    benchmark's own runs never do."""
    cell = load_cell(args.workload, root)
    if not (root / "src" / "repro").is_dir():
        raise BenchError(f"no program (src/repro) under {root}")
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    import jax
    if chip:
        require_chip(cell.chips)
        enable_compile_cache(root)
        peaks = peaks_for(jax.devices()[0].device_kind, cell.bench)
    run = Run(cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), t_start=t_start,
              host={"jax_start_s": time.perf_counter() - t_start})
    outcome = cell.generator().run(run, control=control)
    summary = None
    if run.slice is not None:
        from trace_reduce import extract, summarize, trim
        t = time.perf_counter()
        try:
            rec = extract(run.slice)
        finally:
            shutil.rmtree(run.slice, ignore_errors=True)
        run.trace_s["read"] = time.perf_counter() - t
        if getattr(args, "trace_record", None):
            with open(args.trace_record, "w") as fh:
                json.dump(trim(rec, 5000), fh)
        t = time.perf_counter()
        summary = summarize(rec)
        run.trace_s["reduce"] = time.perf_counter() - t
        print(f"trace: {sum(map(len, rec['ops'].values()))} device "
              "operations; the profiler's seconds: " + ", ".join(
                  f"{k} {v:.3f}s" for k, v in run.trace_s.items()),
              file=sys.stderr)
    line = result_line(cell, run, outcome, peaks=peaks, summary=summary)
    r, h = run.window_compiles
    for note in outcome.notes:
        print(note, file=sys.stderr)
    print(f"setup {run.setup_s:.3f}s: {run.setup_parts()}", file=sys.stderr)
    print("device memory, bytes in use / peak: " + "; ".join(
        f"{label} {a} / {b}" for label, (a, b) in run.memory.items()),
        file=sys.stderr)
    print(f"window {run.window_s:.3f}s, {outcome.attempted} attempted, "
          f"{outcome.failed} failed; in the window: {r} XLA compiles, {h} "
          f"persistent-cache loads", file=sys.stderr)
    for c in outcome.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    if control is not None:
        line["controls"] = control_lines(cell, run, outcome, peaks=peaks)
        line["stats"] = outcome.stats
        for variant, got in line["controls"].items():
            print(f"control {variant}: correct {got['correct']}; " + ", ".join(
                f"{n} {c['value']!r}" for n, c in got["checks"].items()),
                file=sys.stderr)
    return line


def main(argv=None) -> int:
    args = parse(argv)
    try:
        line = execute(args)
    except BenchError as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
