"""Commit walk (types/validation.py): time inside `verify.commit_walk`
(address lookup, the sign-bytes of every signature walked, the batch's
build, the tally) per commit."""

from benchmark.readers import span_ms


def read(ctx):
    ms, n = span_ms(ctx, "verify.commit_walk")
    return ms / n if n else None
