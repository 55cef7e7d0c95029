"""Share of the traced slice in which the device idled while the host
was inside a block-pipeline span (``pipeline.pad``, ``.chunk``,
``.block``, ``.stitch``) and no shorter program span, in percent. Read
from the profiler trace (``span_split``); nothing where the slice holds
no program span."""

from span_split import idle_share


def read(ctx):
    return idle_share(ctx.trace, "pipeline.")
