"""Host prep and transfer (ops/verify.py, ops/msm.py, native/prep.c): time inside
`ops.prep` (the plane's host prep and its precheck) per device launch."""

from benchmark.readers import launches, span_ms


def read(ctx):
    ms, n = span_ms(ctx, "ops.prep")
    launched = launches(ctx)
    return ms / launched if (ms or n) and launched else None
