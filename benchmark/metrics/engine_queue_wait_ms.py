"""Engine (ops/engine.py): submit-to-dispatch wait of the oldest job of
each group, `engine_queue_wait_seconds`, mean over the groups of the
slice."""

from benchmark.readers import ENGINE, counter_delta


def read(ctx):
    n = counter_delta(ctx, ENGINE + "queue_wait_seconds_count")
    return counter_delta(ctx, ENGINE + "queue_wait_seconds_sum") / n * 1e3 if n else None
