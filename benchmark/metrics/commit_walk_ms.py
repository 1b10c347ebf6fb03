"""Commit walk (types/validation.py): time inside `verify.commit_dispatch`
(sign-bytes of every signature walked, the job's build and its
submission) per commit."""

from benchmark.readers import span_ms


def read(ctx):
    ms, n = span_ms(ctx, "verify.commit_dispatch")
    return ms / n if n else None
