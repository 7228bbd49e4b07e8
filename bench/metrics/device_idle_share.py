"""device_idle_share: the share, in %, of the benchmark's span around one
sweep in which no operation ran on the device."""


def read(ctx):
    s = ctx.summary
    if s is None or not s.busy_ns:
        return None
    return 100.0 * s.idle_share
