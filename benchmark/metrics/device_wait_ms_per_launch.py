"""Device: time the collect thread was blocked on a launch's result,
`device.wait` (`block_until_ready` before the read-back), per device
launch: the kernel's time less what the host overlapped."""

from benchmark.readers import launches, span_ms


def read(ctx):
    ms, n = span_ms(ctx, "device.wait")
    launched = launches(ctx)
    return ms / launched if (ms or n) and launched else None
