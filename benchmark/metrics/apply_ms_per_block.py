"""Caller layer (blocksync/reactor.py): time inside the `blocksync.apply`
span (apply_block: the full validation of the last commit, the app, the
stores) over the blocks applied."""

from benchmark.readers import span_ms


def read(ctx):
    ms, n = span_ms(ctx, "blocksync.apply")
    return ms / n if n else None
