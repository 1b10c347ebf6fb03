"""Caller layer (light/client.py `_cross_reference`): time inside
`light.fetch` with `purpose="witness"` (a witness's copy of the target,
fetched to compare its header's hash with the primary's) over the
updates (`light.update`, counted as `fetch_ms_per_update.light` counts
them) that ended in the slice. What the cross-check pays to decode;
where a light block decodes only what is read, a header. None where no
update ended, or the program's `light.fetch` says no `purpose`."""

from benchmark.readers import span_ms


def read(ctx):
    fetches = [sp for sp in ctx["spans"] if sp["name"] == "light.fetch"]
    _, updates = span_ms(ctx, "light.update")
    if not updates or not any("purpose" in sp["args"] for sp in fetches):
        return None
    ns = sum(sp["t1"] - sp["t0"] for sp in fetches if sp["args"].get("purpose") == "witness")
    return ns / 1e6 / updates
