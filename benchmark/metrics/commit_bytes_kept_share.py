"""Caller layer (types/block.py `Commit._encoding`, read through
`blocksync/reactor.py` and `store/blockstore.py`): 100 x the reads of a
commit's bytes served from bytes already made over all of them, in the
`blocksync.parts` and `blocksync.save_block` spans (their `kept` and
`encoded`), summed over the **whole window**. A read is a commit's
bytes in its block's parts, a block's size in the store (read from the
parts, which hold the last commit's bytes, where the block made them),
and the last and the seen commit the store writes. None on a program
whose spans carry no `kept`."""

from benchmark.window_spans import window

SPANS = ("blocksync.parts", "blocksync.save_block")


def read(ctx):
    spans = [sp for sp in window(ctx)["spans"] if sp["name"] in SPANS and "kept" in sp["args"]]
    kept = sum(sp["args"]["kept"] for sp in spans)
    reads = kept + sum(sp["args"].get("encoded", 0) for sp in spans)
    if not reads:
        return None
    return 100.0 * kept / reads
