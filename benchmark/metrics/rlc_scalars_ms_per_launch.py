"""Host prep and transfer (ops/msm.py): time inside `ops.rlc_scalars` (the
randomizer draw and the scalar arithmetic of the RLC check) per device
launch."""

from benchmark.readers import launches, span_ms


def read(ctx):
    ms, n = span_ms(ctx, "ops.rlc_scalars")
    launched = launches(ctx)
    return ms / launched if (ms or n) and launched else None
