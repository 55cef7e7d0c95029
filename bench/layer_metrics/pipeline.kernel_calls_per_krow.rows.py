"""Pallas kernel events on the device per 1,000 required rows in the
traced slice: a count from the trace (one region call per 8-row block
step reads 125)."""


def read(ctx):
    t, rows = ctx.trace, ctx.work.get("rows", 0)
    if t is None or not rows or not t.kernel_calls:
        return None
    return t.kernel_calls / (rows / 1000.0)
