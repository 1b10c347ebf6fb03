"""Kernels: device time of the per-signature programs (every program of
the trace but the MSM's and the harness's anchor: `verify_kernel*`, and
`build_pk_tables*` where a key missed the cache) over the engine's
bitmap-route launches in the slice. `kernel_ms_per_launch` mixes both
device routes; this is the bitmap route's part."""

from benchmark.routes import bitmap_device_s, bitmap_launches


def read(ctx):
    seconds, n = bitmap_device_s(ctx), bitmap_launches(ctx)
    return seconds * 1e3 / n if seconds and n else None
