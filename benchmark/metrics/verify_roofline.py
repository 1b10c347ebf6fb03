"""Kernels: the share of the chip's integer peak that signature
verification reached while a kernel ran. Signatures verified on a
device route in the slice times W (benchmark/work.json: the 32-bit
multiply-adds of one cofactored verification by the textbook method,
whatever kernel ran), two operations each, over kernel device time
times the peak of peaks.json. Compute-bound: a verification moves 128
bytes in and one bit out."""

from benchmark.readers import device_rows


def read(ctx):
    rows = device_rows(ctx)
    if ctx["device"] is None or not rows or not ctx["device"]["kernel_s"]:
        return None
    ops = rows * ctx["work"]["multiply_adds_per_verification"] * 2
    return 100.0 * ops / (ctx["device"]["kernel_s"] * ctx["peaks"]["int8_ops_per_s"])
