"""Kernels: device time of every operation in the profiler's trace (the
harness's anchor op left out) over the engine's device launches in the
slice."""

from benchmark.readers import launches


def read(ctx):
    n = launches(ctx)
    if ctx["device"] is None or not n or not ctx["device"]["kernel_s"]:
        return None
    return ctx["device"]["kernel_s"] * 1e3 / n
