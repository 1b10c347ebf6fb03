"""Caller layer (types/light_block.py under light/client.py): of the
two large parts of every light block fetched (its validator set, its
commit), the share that nothing read and that therefore stayed bytes:
100 x (1 - `light.decode_part` spans / (2 x `light.fetch` spans)), both
counted where they ended in the slice. A block that is validated reads
both; a witness's copy, compared by its header's hash, reads neither.
None where the slice holds no `light.decode_part` span: a program whose
blocks defer nothing."""

from benchmark.readers import span_ms


def read(ctx):
    _, fetches = span_ms(ctx, "light.fetch")
    if not fetches or not any(sp["name"] == "light.decode_part" for sp in ctx["spans"]):
        return None
    _, parts = span_ms(ctx, "light.decode_part")
    return 100.0 * (1.0 - parts / (2.0 * fetches))
