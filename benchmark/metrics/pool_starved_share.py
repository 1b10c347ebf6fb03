"""Caller layer (blocksync/reactor.py): the share of the slice in which
the sync loop had no block to apply, time inside `blocksync.starved`
(from the first poll that found nothing to the next block applied).
0 is a reading: blocks were applied and the loop never went without.
The slice's length is the device trace's; a rehearsal has none and
takes the extent of the slice's spans."""

from benchmark.readers import span_ms


def read(ctx):
    starved_ms, _ = span_ms(ctx, "blocksync.starved")
    polls = [sp for sp in ctx["spans"] if sp["name"] == "blocksync.try_sync"]
    if not polls:
        return None
    if ctx["device"] is not None:
        slice_ms = ctx["device"]["window_s"] * 1e3
    else:
        slice_ms = (max(sp["t1"] for sp in ctx["spans"])
                    - min(sp["t0"] for sp in ctx["spans"])) / 1e6
    return 100.0 * starved_ms / slice_ms
