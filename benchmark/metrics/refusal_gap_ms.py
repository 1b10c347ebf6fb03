"""Caller layer (blocksync/reactor.py), the whole window: what a
refusal costs the verify loop, from the start of each `blocksync.refuse`
to the end of the first `blocksync.try_sync` with `applied` that starts
after it on that thread (eviction, redial, refetch, the first block
applied again); mean over the window's refusals, milliseconds. The run's
log says what each thread's outermost spans did inside each gap. None
where the window held no refusal, or on a program whose spans carry no
`cpu_us`."""

from benchmark.window_spans import refusal_gaps


def read(ctx):
    gaps = refusal_gaps(ctx)
    return sum(g["gap_ns"] for g in gaps) / 1e6 / len(gaps) if gaps else None
