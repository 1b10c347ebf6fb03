"""Commit walk and below, as the caller sees it (types/validation.py):
time inside `verify.commit_dispatch` (a batch's submission) and
`verify.commit_collect` (the wait for its verdicts), every commit of an
update together, per update (`light.update`) that ended in the slice.
What an update pays for verification whatever route the engine took:
the host's C loop, one launch or two in series."""

from benchmark.readers import span_ms


def read(ctx):
    ms, _ = span_ms(ctx, "verify.commit_dispatch", "verify.commit_collect")
    _, updates = span_ms(ctx, "light.update")
    return ms / updates if updates else None
