"""host_ms_per_sweep: the host's share of one sweep, in ms. The
benchmark's span around the device-path call, less the device-busy union
inside it: grid build, transfers, readback and finalize."""


def read(ctx):
    s = ctx.summary
    if s is None or not s.busy_ns:
        return None
    return (s.span_ns - s.busy_ns) / 1e6
