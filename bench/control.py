#!/usr/bin/env python3
"""Read a cell's correctness numbers for the program and for its control,
over several seeds, in one process on the chip.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 3 \
        [--precision high] [--out control.jsonl]

For each seed this makes one run of the cell (a window, then the
comparison with the reference) and also puts the control in the
program's place: the plain reference in ``--precision`` (``high``, three
bf16 passes, one step below the configuration's ``highest``), with any
faults the generator plants in it, each judged against the cell's limits
as a run is.  One JSON line per seed: ``{"seed", "correct", "program":
{number: value}, "limits", "controls": {variant: {"correct", number:
value}}, "stats"}``.  The benchmark's own runs never run this; its limits
are set from these readings (PERF.md).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--precision", default="high")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    import run as bench_run
    out = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            a = SimpleNamespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0,
                                trace_record=None)
            try:
                line = bench_run.execute(
                    a, t_start=time.perf_counter(),
                    control={"precision": args.precision})
            except bench_run.BenchError as e:
                print(f"control: {e}", file=sys.stderr)
                return 2
            rec = {"seed": seed, "workload": args.workload,
                   "correct": line["correct"],
                   "program": {k: v["value"]
                               for k, v in line["checks"].items()},
                   "limits": {k: v["limit"]
                              for k, v in line["checks"].items()},
                   "controls": {k: {"correct": v["correct"],
                                    **{n: c["value"] for n, c
                                       in v["checks"].items()}}
                                for k, v in line["controls"].items()},
                   "stats": line["stats"],
                   "metrics": {k: v["value"]
                               for k, v in line["metrics"].items()}}
            print(json.dumps(rec), flush=True)
            if out:
                out.write(json.dumps(rec) + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
