"""Share of its roofline that the ``system_sim`` kernel reached: the least time the
bytes of its engine calls take at the chip's HBM peak (``bench/kernels.py``,
``bench/peaks.json``) over the kernel's device time in the trace.  The
kernel is bound by memory traffic, not operations."""

KERNEL = "system_sim"


def read(ctx):
    if ctx.trace is None:
        return None
    secs, n = ctx.trace.module_seconds(ctx.kernels.PROGRAMS[KERNEL])
    calls = [c for c in ctx.calls if c["kernel"] == KERNEL]
    if n == 0 or secs <= 0 or not calls:
        return None
    least = sum(ctx.kernels.call_bytes(c) for c in calls) / ctx.peaks()["hbm_bytes_per_s"]
    return 100.0 * least / secs
