"""Host milliseconds spent enqueueing the block pipeline's chunks and
remainder blocks (``pipeline.chunk`` and ``pipeline.block`` spans,
clipped to the traced slice) per 1,000 required rows of the slice.  Read
from the profiler trace (``span_split``); nothing where the slice holds
no such span."""


def read(ctx):
    t, rows = ctx.trace, ctx.work.get("rows", 0)
    span_s = getattr(t, "span_s", None) or {}
    enqueue = [span_s[k][1] for k in ("pipeline.chunk", "pipeline.block")
               if k in span_s]
    if not rows or not enqueue:
        return None
    return 1000.0 * sum(enqueue) / (rows / 1000.0)
