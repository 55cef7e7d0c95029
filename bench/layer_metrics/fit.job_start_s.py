"""Host seconds from the window job's ``fit`` call to its first counted
step: the fit engine's fixed cost per job (its preparation of the points,
its step's re-trace and compile or cache load, and the first step)."""


def read(ctx):
    return ctx.host.get("job_start_s")
