"""The whole step's share of the chip's bf16 peak: the slice's required
operations (``work.required``) over the slice's wall time, over one
chip's peak FLOP/s.  Moves with the end-to-end rate whatever kernel does
the work."""

import work


def read(ctx):
    req = work.required(ctx)
    wall = ctx.work.get("wall_s", 0.0)
    if req is None or wall <= 0:
        return None
    return 100.0 * req[0] / wall / ctx.peaks["bf16_flops_per_s"]
