"""tick_loop_roofline: the least time the chip could take for one sweep,
the least bytes (`bench.roofline.least_bytes`) over peak HBM bandwidth,
as a share in % of the sweep's device-busy time. Bound by bytes: the
tick loop does no floating-point work."""


def read(ctx):
    s = ctx.summary
    if s is None or not s.busy_ns:
        return None
    least_s = ctx.least_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (s.busy_ns / 1e9)
