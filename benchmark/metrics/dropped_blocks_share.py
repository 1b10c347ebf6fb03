"""Caller layer (blocksync/pool.py `remove_peer`, `redo_request`):
blocks the pool had received, not yet verified, and threw away with the
peer that sent them (`blocksync_blocks_dropped_total`) over the blocks
it received (`blocksync_blocks_received_total`), the whole window. Each
is a 1000-signature block decoded on the receive thread for nothing and
fetched again. None where nothing was received or the driver hands no
such counter over."""


def read(ctx):
    window = ctx["window"]
    if not window.get("blocks_received"):
        return None
    return 100.0 * window["blocks_dropped"] / window["blocks_received"]
