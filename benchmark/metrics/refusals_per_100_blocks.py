"""Caller layer (blocksync/reactor.py `_refuse`): pairs of heights the
joiners refused over the whole window (`blocksync_refusals_total`, both
stages) per 100 blocks applied. Each is two peers evicted, their
unverified blocks thrown away and two heights fetched again. The whole
window, not the slice: a slice holds one refusal or none. None where
the driver hands no such counter over."""


def read(ctx):
    window = ctx["window"]
    if "refusals_commit" not in window or not window["ops"]:
        return None
    return 100.0 * (window["refusals_commit"] + window["refusals_block"]) / window["ops"]
