"""tick_loop_useful_share: the traced sweep's cell ticks (the numerator of
`sim_ticks_per_s`) over the lane-ticks the lock-step loop stepped, the
counters `cells` x `loop_iterations`, in %. The rest steps cells that
have finished while the slowest runs on."""


def read(ctx):
    s = getattr(ctx, "spans", None)
    cells = s and s.counter("cells")
    n = s and s.counter("loop_iterations")
    if not cells or not n:
        return None
    return 100.0 * ctx.sum_cell_ticks / (cells * n)
