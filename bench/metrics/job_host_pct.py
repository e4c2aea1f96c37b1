"""Share of the measured window spent outside engine calls: turning results
into figure numbers and handing jobs over (host clock around each engine
call)."""


def read(ctx):
    if ctx.window_s <= 0 or not ctx.calls:
        return None
    return 100.0 * (1.0 - sum(c["seconds"] for c in ctx.calls) / ctx.window_s)
