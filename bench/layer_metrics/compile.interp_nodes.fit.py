"""Graph nodes the fit runs on the program's per-node interpreter, outside
every Pallas kernel: the program's ``compile_interp_nodes`` counter
(``path="fit"``) over the set-up's ``compile_fit``, read by the generator
into the run's host readings.  0 when every node runs in a kernel."""


def read(ctx):
    return ctx.host.get("compile_interp_nodes")
