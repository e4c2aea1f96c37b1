"""Share of the engine calls' time in which the device is idle: the
orchestrator's and streams' host work (key views, uploads, pulling results
back, chunk bookkeeping), from the benchmark's ``bench.engine:*`` spans and
the device operations in the profiler trace."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.devices:
        return None
    spans = t.spans_named("bench.engine:")
    total = sum(e - s for s, e in spans) * 1e-9
    if total <= 0:
        return None
    return 100.0 * (1.0 - t.busy_within(spans) / total)
