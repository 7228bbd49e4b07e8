"""tick_arbitrate_us_per_tick: device own time of the operations under the
scope `tick.arbitrate` inside the program span `sweep.tick_loop`, in us,
over the times the loop ran (the counter `loop_iterations`)."""


def read(ctx):
    s = getattr(ctx, "spans", None)
    n = s and s.counter("loop_iterations")
    if not n or "tick.arbitrate" not in s.scope_ns:
        return None
    return s.scope_ns["tick.arbitrate"] / 1e3 / n
