"""Caller layer (blocksync/pool.py, p2p/router.py, p2p/peermanager.py):
from a refusal that removed a peer from the pool to the status that
brought it back (`blocksync_peer_out_seconds_total` over
`blocksync_peer_returns_total`; the span `blocksync.peer_out`):
eviction, disconnect, redial, handshake, status request and response.
While it lasts the joiner syncs from fewer peers, and when it ends a
liar lies again. The whole window; 0 where no blamed peer returned;
None where the driver hands no such counter over."""


def read(ctx):
    window = ctx["window"]
    if "peer_returns" not in window:
        return None
    return 1e3 * window["peer_out_s"] / window["peer_returns"] if window["peer_returns"] else 0.0
