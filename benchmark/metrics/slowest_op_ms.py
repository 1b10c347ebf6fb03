"""Caller layer, the whole window: the longest request root of the run
(`light.update`; a `blocksync.try_sync` that applied a block), in
milliseconds. In a light cell it is the driver's largest latency; a
multi-second reading is a stall, and the run's log names the root, when
it began and the longest span at each level below it
(`benchmark/window_spans.py`).
None on a program whose spans carry no `cpu_us`."""

from benchmark.window_spans import slowest


def read(ctx):
    found = slowest(ctx)
    return found["ms"] if found else None
