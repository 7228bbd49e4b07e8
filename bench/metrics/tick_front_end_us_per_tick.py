"""tick_front_end_us_per_tick: device own time of the operations under the
scope `tick.front_end` inside the program span `sweep.tick_loop`, in us,
over the times the loop ran (the counter `loop_iterations`)."""


def read(ctx):
    s = getattr(ctx, "spans", None)
    n = s and s.counter("loop_iterations")
    if not n or "tick.front_end" not in s.scope_ns:
        return None
    return s.scope_ns["tick.front_end"] / 1e3 / n
