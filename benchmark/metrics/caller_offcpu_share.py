"""Caller layer: the share of the requests' roots (`light.update`; a
`blocksync.try_sync` that applied a block) in which their thread was off
the CPU without meaning to be: 100 x (the roots' durations less their
`cpu_us`, less the same of the `verify.commit_collect` spans of their
`req` on their thread, the one wait by design) over the roots' duration,
every root of the **whole window**. What is left is the GIL held by
another thread, another lock, or a core another tenant held. None on a
program whose spans carry no `cpu_us`."""

from benchmark.window_spans import roots, unmeant_offcpu_share, window


def read(ctx):
    spans = window(ctx)["spans"]
    return unmeant_offcpu_share(roots(spans), spans)
