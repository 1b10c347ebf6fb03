"""Host prep and transfer: time inside `device.h2d` (the staging calls,
`jnp.asarray` of a launch's arguments; a copy still in flight when they
return is not in it) per device launch."""

from benchmark.readers import launches, span_ms


def read(ctx):
    ms, n = span_ms(ctx, "device.h2d")
    launched = launches(ctx)
    return ms / launched if (ms or n) and launched else None
