"""Host prep and transfer (ops/verify.py, ops/msm.py, native/prep.c):
time inside `ops.verify_dispatch` and `ops.msm_dispatch` (prep, H2D,
the asynchronous launch) per launch."""

from benchmark.readers import span_ms


def read(ctx):
    ms, n = span_ms(ctx, "ops.verify_dispatch", "ops.msm_dispatch")
    return ms / n if n else None
