"""Benchmark harness: one module per paper table + roofline + kernels.

Prints ``name,us_per_call,derived`` CSV lines.

  PYTHONPATH=src python -m benchmarks.run [table1 table2 ... autotune] [--json]

With ``--json``, each benchmark additionally writes a machine-readable
record to ``results/<name>.json``: every emitted row (plus any structured
extras — resolved HardwareConfig dicts, predicted latencies, memory bytes)
wrapped with the backend and timestamp, for CI trending and regression
tracking.

With ``--check`` (implies ``--json``), benchmarks that declare a ``check``
hook are gated against their committed ``results/<name>_baseline.json``:
deterministic compiler metrics (dispatch counts, predicted HBM bytes) that
regress vs the baseline fail the run — ci.sh wires ``regions`` through this.
"""

import json
import pathlib
import sys
import time

from repro.compile_cache import enable_compile_cache

from benchmarks import (autotune_bench, bank_bench, common, fit_bench,
                        higher_order, kernels_bench, obs_bench,
                        pipeline_bench, regions_bench, roofline,
                        segments_bench, serve_bench, table1_latency,
                        table2_parallelism, table3_graphopt, table4_fifo)

ALL = {
    "table1": table1_latency.run,
    "table2": table2_parallelism.run,
    "table3": table3_graphopt.run,
    "table4": table4_fifo.run,
    "roofline": roofline.run,
    "kernels": kernels_bench.run,
    "segments": segments_bench.run,
    "regions": regions_bench.run,
    "bank": bank_bench.run,
    "fit": fit_bench.run,
    "pipeline": pipeline_bench.run,
    "autotune": autotune_bench.run,
    "serve": serve_bench.run,
    "obs": obs_bench.run,
    "higher_order": higher_order.run,       # opt-in: ~3 min FIFO search
}
DEFAULT = [n for n in ALL if n != "higher_order"]

# regression gates: benchmark name -> check(current_records, baseline) hook
CHECKS = {
    "regions": regions_bench.check,
    "bank": bank_bench.check,
    "fit": fit_bench.check,
    "obs": obs_bench.check,
}

RESULTS_DIR = pathlib.Path(__file__).resolve().parent.parent / "results"


def write_json(name: str, records: list[dict]) -> pathlib.Path:
    import jax

    RESULTS_DIR.mkdir(exist_ok=True)
    path = RESULTS_DIR / f"{name}.json"
    payload = {
        "benchmark": name,
        "backend": jax.default_backend(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "results": records,
    }
    path.write_text(json.dumps(payload, indent=2, default=str) + "\n")
    return path


def check_baseline(name: str, records: list[dict]) -> list[str]:
    """Run a benchmark's regression gate against its committed baseline.
    A missing baseline file is not a failure (first run commits one)."""
    hook = CHECKS.get(name)
    if hook is None:
        return []
    path = RESULTS_DIR / f"{name}_baseline.json"
    if not path.is_file():
        if getattr(hook, "self_gated", False):
            return hook(records, {})       # gates the run itself, no baseline
        print(f"# no baseline at {path}; skipping check", flush=True)
        return []
    baseline = json.loads(path.read_text())
    return hook(records, baseline)


def main() -> None:
    args = sys.argv[1:]
    flags = [a for a in args if a.startswith("-")]
    names = [a for a in args if not a.startswith("-")]
    bad_flags = [f for f in flags if f not in ("--json", "--check")]
    bad_names = [n for n in names if n not in ALL]
    if bad_flags or bad_names:
        bad = " ".join(bad_flags + bad_names)
        sys.exit(f"benchmarks.run: unknown argument(s): {bad}\n"
                 f"usage: python -m benchmarks.run "
                 f"[{' | '.join(ALL)}] [--json] [--check]")
    as_check = "--check" in flags
    as_json = "--json" in flags or as_check
    which = names or DEFAULT
    failures: list[str] = []
    enable_compile_cache()
    print("name,us_per_call,derived")
    for name in which:
        common.drain_results()
        ALL[name]()
        records = common.drain_results()
        if as_json:
            path = write_json(name, records)
            print(f"# wrote {path}", flush=True)
        if as_check:
            fails = check_baseline(name, records)
            for f in fails:
                print(f"# CHECK FAILED {name}: {f}", flush=True)
            failures += fails
    if failures:
        sys.exit(f"benchmarks.run --check: {len(failures)} regression(s) "
                 f"vs committed baseline")


if __name__ == '__main__':
    main()
