"""finalize_ms: the length of the program span `sweep.finalize` in the
traced sweep, in ms: the `CellResult`s."""


def read(ctx):
    s = getattr(ctx, "spans", None)
    if s is None or "sweep.finalize" not in s.spans:
        return None
    return s.spans["sweep.finalize"].ns / 1e6
