"""Caller layer (types/light_block.py under light/client.py): time
inside `light.decode_part` (a light block's validator set or commit
turned from its bytes into objects, the first time something reads it)
over the updates (`light.update`, counted as `fetch_ms_per_update.light`
counts them) that ended in the slice. The part of a fetch that is
decoding; what is left of `fetch_ms_per_update.light` is the provider,
the header and `validate_basic`. None where no update ended, or the
slice holds no `light.decode_part` span: a program whose blocks defer
nothing."""

from benchmark.readers import span_ms


def read(ctx):
    _, updates = span_ms(ctx, "light.update")
    if not updates or not any(sp["name"] == "light.decode_part" for sp in ctx["spans"]):
        return None
    decode_ms, _ = span_ms(ctx, "light.decode_part")
    return decode_ms / updates
