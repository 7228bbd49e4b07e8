"""tick_loop_us_per_tick: device-busy time of one sweep, in us, over the
grid's largest cell tick count, which a lock-step loop must step through
whatever implements it."""


def read(ctx):
    s = ctx.summary
    if s is None or not s.busy_ns:
        return None
    return s.busy_ns / 1e3 / ctx.max_cell_ticks
