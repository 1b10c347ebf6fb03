"""Caller layer: the median wall time of an update over the whole
window, beside the end-to-end 95th percentile."""

import statistics


def read(ctx):
    latencies = ctx["window"].get("latencies_ms")
    return statistics.median(latencies) if latencies else None
