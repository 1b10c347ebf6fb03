"""Caller layer: what no span below the request's root accounts for.
The self time of the root span (`light.update` for a light client,
`blocksync.try_sync` that applied a block for a joiner: its duration
less what its children on the same thread cover), per update or block
that ended in the slice. A cell has one kind of root, so each of the
two metrics of this name finds only its own."""

from benchmark.selftime import self_ms_per_op


def read(ctx):
    light = self_ms_per_op(ctx, "light.update")
    if light is not None:
        return light
    return self_ms_per_op(ctx, "blocksync.try_sync", lambda sp: sp["args"].get("applied"))
