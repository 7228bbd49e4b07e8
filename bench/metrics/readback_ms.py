"""readback_ms: the length of the program span `sweep.readback` in the
traced sweep, in ms: `jax.device_get` of the final state, after the loop
has finished on the device."""


def read(ctx):
    s = getattr(ctx, "spans", None)
    if s is None or "sweep.readback" not in s.spans:
        return None
    return s.spans["sweep.readback"].ns / 1e6
