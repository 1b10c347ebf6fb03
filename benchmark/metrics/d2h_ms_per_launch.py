"""Host prep and transfer: time inside `device.d2h` per device launch: the
read-back of a result the kernel has finished. A program older than
`device.wait` waited for the kernel inside this span."""

from benchmark.readers import launches, span_ms


def read(ctx):
    ms, n = span_ms(ctx, "device.d2h")
    launched = launches(ctx)
    return ms / launched if (ms or n) and launched else None
