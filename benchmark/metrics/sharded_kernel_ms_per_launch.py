"""Kernels: device time of the sharded per-signature program
(`jit_sharded_verify`), one chip's mean over the chips it ran on, per
launch of the engine's sharded route in the slice. Silent where no
sharded launch ended in the slice."""

from benchmark import sharded


def read(ctx):
    seconds, n = sharded.device_s(ctx), sharded.launches(ctx)
    return seconds * 1e3 / n if seconds and n else None
