"""Caller layer (light/client.py): time inside `light.fetch` (a
provider's light block fetched and decoded, its basic validation) over
the updates (`light.update`) that ended in the slice."""

from benchmark.readers import span_ms


def read(ctx):
    fetch_ms, _ = span_ms(ctx, "light.fetch")
    _, updates = span_ms(ctx, "light.update")
    return fetch_ms / updates if updates else None
