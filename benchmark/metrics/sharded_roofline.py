"""Kernels: the share of the mesh's integer peak that the sharded route
reached while its program ran. Signatures verified on the route in the
slice times W (benchmark/work.json, the same W whatever kernel ran:
benchmark/WORK.md), two operations each, over the program's device time
(a chip's mean) times the chips it ran on (`shards` of its dispatch
spans) times one chip's peak (peaks.json). Compute-bound, as
`verify_roofline`, whose denominator is one chip's. Silent where no
sharded launch ended in the slice."""

from benchmark import sharded


def read(ctx):
    seconds, rows, spans = sharded.device_s(ctx), sharded.rows(ctx), sharded.dispatches(ctx)
    if not seconds or not rows or not spans or not sharded.launches(ctx):
        return None
    chips = max(sp["args"]["shards"] for sp in spans)
    ops = rows * ctx["work"]["multiply_adds_per_verification"] * 2
    return 100.0 * ops / (seconds * chips * ctx["peaks"]["int8_ops_per_s"])
