"""Kernels: the share of the chip's integer peak that the per-signature
route reached while its programs ran: signatures verified on the bitmap
route in the slice times W (benchmark/work.json, the same W whatever
kernel ran: benchmark/WORK.md), two operations each, over those
programs' device time times the peak of peaks.json. Compute-bound, as
`verify_roofline`, of which this is the bitmap route's part."""

from benchmark.routes import bitmap_device_s, bitmap_rows


def read(ctx):
    seconds, rows = bitmap_device_s(ctx), bitmap_rows(ctx)
    if not seconds or not rows:
        return None
    ops = rows * ctx["work"]["multiply_adds_per_verification"] * 2
    return 100.0 * ops / (seconds * ctx["peaks"]["int8_ops_per_s"])
