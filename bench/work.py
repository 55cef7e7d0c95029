"""The work a traced slice was required to do, from the benchmark's own
counts: rows the traffic asked for (unique rows: a bank call's five
requests over one tile are one tile of required work) times the plain
reference's operations per row (``flops_per_row`` of the configuration,
under the traffic's ``work`` key), and the bytes that must cross HBM at
the least: each required row's ``row_cols`` float32 columns in and out,
plus the weights ``weight_passes`` times per call (read; for a fit step,
read and the gradient written)."""

from __future__ import annotations


def required(ctx) -> tuple[float, float] | None:
    """(flops, bytes) of the slice, or None when the slice did no work."""
    rows, calls = ctx.work.get("rows", 0), ctx.work.get("calls", 0)
    if not rows:
        return None
    tr = ctx.traffic
    flops = rows * ctx.config["flops_per_row"][tr["work"]]
    nbytes = (rows * 4 * tr["row_cols"]
              + calls * tr["weight_passes"]
              * ctx.reference.param_bytes(ctx.config))
    return flops, nbytes
