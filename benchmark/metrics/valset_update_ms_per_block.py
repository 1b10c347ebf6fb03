"""Caller layer (state/state.py `State.update`, under
`state.apply_block`): milliseconds inside the `state.update` span per
block applied, over the **whole window**: the three validator sets
copied, the block's validator updates folded into the next one (the
span's `changes`), its proposer priorities moved on. None on a program
without the span."""

from benchmark.metrics.state_save_ms_per_block import ms_per_block


def read(ctx):
    return ms_per_block(ctx, "state.update")
