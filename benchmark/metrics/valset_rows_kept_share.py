"""Caller layer (state/store.py `StateStore.save`, under
`state.apply_block`): 100 x the validator rows whose fixed part (address,
key, power) a save took from the validator's memo over all the rows it
encoded (the `state.save` span's `rows_kept` and `rows`: validators and
proposers of every set written whole), summed over the **whole window**.
A joiner's genesis state (`height` 0) is left out. None on a program
whose span carries no `rows`."""

from benchmark.window_spans import window


def read(ctx):
    spans = [sp for sp in window(ctx)["spans"]
             if sp["name"] == "state.save" and sp["args"].get("height", 0) > 0 and "rows" in sp["args"]]
    rows = sum(sp["args"]["rows"] for sp in spans)
    if not rows:
        return None
    return 100.0 * sum(sp["args"].get("rows_kept", 0) for sp in spans) / rows
