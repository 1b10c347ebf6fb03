"""Caller layer (blocksync/reactor.py), the whole window: 100 x the
time inside `blocksync.starved` (the loop polling a pool that has no
two blocks for it) within `refusal_gap_ms`'s gaps over those gaps. The
rest is the refusing iteration itself and the first block applied."""

from benchmark.window_spans import refusal_gaps


def read(ctx):
    gaps = refusal_gaps(ctx)
    total = sum(g["gap_ns"] for g in gaps)
    return 100.0 * sum(g["starved_ns"] for g in gaps) / total if total else None
