"""Caller layer (light/client.py, light/verifier.py): the wall time of
an update (the benchmark's `bench.update` span around the client's
call) less the time it spent waiting for verdicts inside
`verify.commit_collect`, mean over the updates that ended in the slice:
fetch and decode, hashing, the commit walk, the store."""

from benchmark.readers import span_ms


def read(ctx):
    update_ms, n = span_ms(ctx, "bench.update")
    waited_ms, _ = span_ms(ctx, "verify.commit_collect")
    return (update_ms - waited_ms) / n if n else None
