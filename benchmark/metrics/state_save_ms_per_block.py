"""Caller layer (state/store.py `StateStore.save`, under
`state.apply_block`): milliseconds inside the `state.save` span per
block applied, over the **whole window**: the state and the validator
and params entries written after each block, one JSON document each, a
validator set encoded whole where the block changed it (the span's
`full_sets_written`). A joiner's genesis state, saved before its first
block (`height` 0), is left out. None on a program without the span."""

from benchmark.window_spans import window


def ms_per_block(ctx, name: str) -> float | None:
    """Mean milliseconds of the window's `name` spans of a block
    (`height` above 0): one such span a block applied."""
    spans = [sp for sp in window(ctx)["spans"]
             if sp["name"] == name and sp["args"].get("height", 0) > 0]
    if not spans:
        return None
    return sum(sp["t1"] - sp["t0"] for sp in spans) / 1e6 / len(spans)


def read(ctx):
    return ms_per_block(ctx, "state.save")
