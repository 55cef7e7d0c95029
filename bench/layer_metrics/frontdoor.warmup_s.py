"""Host seconds of the warm-up during set-up: the first calls on the
cell's own shapes, XLA and Mosaic compiles (or persistent-cache loads)
included."""


def read(ctx):
    return ctx.host.get("warmup_s") or None
