"""Device nanoseconds of the ``system_sim`` kernel per simulated (configuration or
simulation) x access, over the traced window."""

KERNEL = "system_sim"


def read(ctx):
    if ctx.trace is None:
        return None
    secs, n = ctx.trace.module_seconds(ctx.kernels.PROGRAMS[KERNEL])
    work = sum(c["work"] for c in ctx.calls if c["kernel"] == KERNEL)
    if n == 0 or work == 0:
        return None
    return 1e9 * secs / work
