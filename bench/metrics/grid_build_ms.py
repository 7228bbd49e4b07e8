"""grid_build_ms: the length of the program span `sweep.grid_build` in the
traced sweep, in ms: grid build (`_Grid`)."""


def read(ctx):
    s = getattr(ctx, "spans", None)
    if s is None or "sweep.grid_build" not in s.spans:
        return None
    return s.spans["sweep.grid_build"].ns / 1e6
