"""stage_ms: the length of the program span `sweep.stage` in the traced
sweep, in ms: constant planes to the device and the initial state."""


def read(ctx):
    s = getattr(ctx, "spans", None)
    if s is None or "sweep.stage" not in s.spans:
        return None
    return s.spans["sweep.stage"].ns / 1e6
