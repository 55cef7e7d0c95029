"""Host seconds in the program's compile front doors (``compile_gradient``,
``filter_bank``, ``compile_fit``) during set-up: trace, optimise, plan,
residents.  XLA's compiles happen at the first calls, in warm-up."""


def read(ctx):
    return ctx.host.get("compile_s") or None
