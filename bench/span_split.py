"""The device's idle time in a traced slice, split by the program's spans.

The program opens its host phases as JAX profiler annotations on the
same clock as the device's operations: ``pipeline.*`` (the block
pipeline's pad, per-chunk and per-block enqueue, stitch), ``serve.*``
(the engines' group, pad, dispatch, wait, unpad) and ``fit.*`` (a fit
step's gather, dispatch, sync).  ``spans`` reads them from a trace;
``split`` assigns every idle instant of the slice to the innermost
(shortest) program span open over it, or to ``"outside"``.

A record is ``trace_reduce``'s (``slice``, ``ops``) with a ``spans`` key
of ``[[name, start_ns, duration_ns], ...]``.  The idle time is averaged
over the device planes as ``trace_reduce.summarize`` averages busy time,
so the split sums to the slice's length less its busy time.
"""

from __future__ import annotations

import glob
import os

from trace_reduce import _union

PREFIXES = ("pipeline.", "serve.", "fit.")
OUTSIDE = "outside"


def spans(trace_dir: str) -> list:
    """The program's spans on the host planes of the newest trace under
    ``trace_dir``, as ``[[name, start_ns, duration_ns], ...]``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return [[e.name, int(e.start_ns), int(e.duration_ns)]
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIXES)]


def _innermost(spans_, lo: int, hi: int) -> list:
    """``[[a, b, name], ...]``: [lo, hi] cut where any span opens or
    closes, each piece named by the shortest span covering it."""
    cuts = sorted({lo, hi} | {t for _, s, d in spans_ for t in (s, s + d)
                              if lo < t < hi})
    pieces = []
    for a, b in zip(cuts, cuts[1:]):
        cover = [(d, n) for n, s, d in spans_ if s <= a and b <= s + d]
        pieces.append([a, b, min(cover)[1] if cover else OUTSIDE])
    return pieces


def _meet(xs, ys):
    """``[a, b, name]`` for each overlap of ``[a, b]`` intervals in ``xs``
    with ``[a, b, name]`` pieces in ``ys``; both sorted and disjoint."""
    i = j = 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            yield a, b, ys[j][2]
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1


def split(rec: dict) -> tuple[dict, dict]:
    """``(idle_by_span, span_s)`` of a record: seconds of device idle per
    innermost span name (``"outside"`` where none was open), and per span
    name ``[count, seconds]`` of the spans that overlap the slice, clipped
    to it."""
    lo, hi = rec["slice"]
    inside = [[n, s, d] for n, s, d in rec.get("spans", ())
              if s < hi and s + d > lo]
    span_s: dict = {}
    for n, s, d in inside:
        got = span_s.setdefault(n, [0, 0.0])
        got[0] += 1
        got[1] += (min(s + d, hi) - max(s, lo)) / 1e9
    pieces = _innermost(inside, lo, hi)
    devices = [rec["ops"][k] for k in sorted(rec["ops"])] or [[]]
    idle: dict = {}
    for ops in devices:
        busy = _union([(max(s, lo), min(s + d, hi)) for _, s, d, _ in ops
                       if min(s + d, hi) > max(s, lo)])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for a, b, name in _meet(gaps, pieces):
            idle[name] = idle.get(name, 0) + (b - a)
    n = len(devices)
    return {k: v / n / 1e9 for k, v in idle.items()}, span_s


def idle_share(trace, prefix: str):
    """Percent of the slice the device idled under spans named
    ``prefix*``: what the idle-share readers report.  None where the
    summary carries no program span (a program without these spans)."""
    idle = getattr(trace, "idle_by_span", None)
    if not getattr(trace, "span_s", None) or trace.window_s <= 0:
        return None
    return 100.0 * sum(v for k, v in idle.items()
                       if k.startswith(prefix)) / trace.window_s
