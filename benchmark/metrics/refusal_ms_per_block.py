"""Caller layer (blocksync/reactor.py `_try_sync_one`): milliseconds
the verify loop spent in iterations that ended in a refusal
(`blocksync_refusal_seconds_total`: the parts, the commit's walk and
launch or the wait for one dispatched ahead, the bans) over the whole
window, per block applied: what the lies cost the loop itself, beside
what they cost the pool. None where the driver hands no such counter
over."""


def read(ctx):
    window = ctx["window"]
    if "refusal_s" not in window or not window["ops"]:
        return None
    return 1e3 * window["refusal_s"] / window["ops"]
