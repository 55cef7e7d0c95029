"""The Pallas (Mosaic) kernels' share of their roofline over the traced
slice: the least time the chip could take for the slice's required work,
the larger of operations over peak FLOP/s and bytes over peak bandwidth
(``work.required``), divided by the summed device time of the slice's
Pallas kernel events.  Nothing is returned when the slice ran no kernel
or did no work."""

import work


def read(ctx):
    t, req = ctx.trace, work.required(ctx)
    if t is None or req is None or t.kernel_s <= 0:
        return None
    flops, nbytes = req
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t.kernel_s
