"""From the JAX profiler's trace of a slice to the numbers the per-layer
metrics read.

``extract`` reads the ``.xplane.pb`` the profiler wrote (with nothing but
``jax.profiler.ProfileData``) into a small JSON-able record:

  slice   [start_ns, end_ns]: from the end of the benchmark's
          ``bench.slice_start`` host span to the start of its
          ``bench.slice_stop`` span
  ops     [[name, start_ns, duration_ns, is_kernel], ...] per device: the
          operations on the device's ``XLA Ops`` line, by HLO instruction
          name; ``is_kernel`` marks a Pallas (Mosaic) kernel, a
          ``tpu_custom_call``
  host    [[name, start_ns, duration_ns], ...]: the benchmark's own
          ``bench.*`` host spans, on the same clock
  lines   {plane: [line, ...]}: what else the trace held

``summarize`` reduces such a record; ``tests/bench`` checks it on a small
record kept in ``testdata``.
"""

from __future__ import annotations

import glob
import json
import os
from dataclasses import dataclass

SLICE_START, SLICE_STOP = "bench.slice_start", "bench.slice_stop"
OPS_LINE = "XLA Ops"


def _op(text: str) -> tuple[str, bool]:
    """An ``XLA Ops`` event's HLO instruction text -> (instruction name,
    whether it is a Pallas kernel: a ``tpu_custom_call``)."""
    return text.split(" = ", 1)[0].lstrip("%"), "tpu_custom_call" in text


def extract(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    rec = {"slice": None, "ops": {}, "host": [], "lines": {}}
    for plane in data.planes:
        rec["lines"][plane.name] = [line.name for line in plane.lines]
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name, kernel = _op(e.name)
                    ops.append([name, int(e.start_ns), int(e.duration_ns),
                                kernel])
            rec["ops"][plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        rec["host"].append([e.name, int(e.start_ns),
                                            int(e.duration_ns)])
    marks = {name: (start, start + dur) for name, start, dur in rec["host"]
             if name in (SLICE_START, SLICE_STOP)}
    if len(marks) == 2:
        rec["slice"] = [marks[SLICE_START][1], marks[SLICE_STOP][0]]
    return rec


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _self_times(intervals) -> dict:
    """Per op name, its time less the time of the ops nested inside it
    (a loop's event spans the ops of its body on the same line)."""
    own: dict = {}
    stack: list = []                      # [end, name] of open ancestors
    for a, b, name in sorted(intervals, key=lambda x: (x[0], -x[1])):
        while stack and (stack[-1][0] <= a or stack[-1][0] < b):
            stack.pop()                   # ended, or overlaps: not nested
        own[name] = own.get(name, 0) + (b - a)
        if stack:
            parent = stack[-1][1]
            own[parent] = own.get(parent, 0) - (b - a)
        stack.append([b, name])
    return own


@dataclass
class TraceSummary:
    devices: int             # device planes that ran operations
    window_s: float          # the slice's length
    busy_s: float            # device busy, averaged over the devices
    kernel_s: float          # summed Pallas kernel time (all devices)
    kernel_calls: int        # Pallas kernel events (all devices)
    top_ops: list            # [[name, self seconds], ...] most first
    gaps: list               # [[label, seconds], ...] longest first

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops[:10], "idle_gaps": self.gaps[:10]}


def _label(host, lo: int, hi: int) -> str:
    """The benchmark's innermost host span over the gap's midpoint."""
    mid = (lo + hi) // 2
    inner = [(d, n) for n, s, d in host
             if n not in (SLICE_START, SLICE_STOP) and s <= mid <= s + d]
    return min(inner)[1] if inner else "outside any call"


def summarize(rec: dict) -> TraceSummary:
    if rec["slice"] is None:
        raise ValueError("the trace marks no slice")
    lo, hi = rec["slice"]
    busy, kernel_ns, calls, per_op, gaps = [], 0, 0, {}, []
    for device in sorted(rec["ops"]):
        clipped = []
        for name, s, d, is_kernel in rec["ops"][device]:
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            clipped.append((a, b, name))
            if is_kernel:
                kernel_ns += b - a
                calls += 1
        for name, ns in _self_times(clipped).items():
            per_op[name] = per_op.get(name, 0) + ns
        merged = _union([(a, b) for a, b, _ in clipped])
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append([_label(rec["host"], a, b), (b - a) / 1e9])
    n = max(len(rec["ops"]), 1)
    top = sorted(([k, v / 1e9] for k, v in per_op.items()),
                 key=lambda kv: -kv[1])
    return TraceSummary(devices=sum(1 for v in rec["ops"].values() if v),
                        window_s=(hi - lo) / 1e9,
                        busy_s=sum(busy) / n / 1e9,
                        kernel_s=kernel_ns / 1e9, kernel_calls=calls,
                        top_ops=top, gaps=sorted(gaps, key=lambda g: -g[1]))


def trim(rec: dict, n: int) -> dict:
    """A smaller record: the first ``n`` operations of each device, the
    slice cut at the last one kept (for keeping a recorded slice)."""
    ops = {k: v[:n] for k, v in rec["ops"].items()}
    ends = [s + d for v in ops.values() for _, s, d, _ in v]
    lo, hi = rec["slice"]
    if ends and max(ends) < hi:
        hi = max(ends)
    host = [h for h in rec["host"] if h[1] < hi]
    return dict(rec, ops=ops, host=host, slice=[lo, hi])


def load_record(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)
