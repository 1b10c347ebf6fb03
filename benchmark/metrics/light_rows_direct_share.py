"""Caller layer (types/light_block.py `_Deferred.read`): of the
validators and commit signatures built by the `light.decode_part` spans
that ended in the slice (`rows`), the share built straight from the
part's bytes in one pass (`path="direct"`) and not from a `pb` message
decoded first (`path="message"`). 100 where every block came off the
wire, as the benchmark's providers serve them. None where no such span
ended in the slice, none of them built a row, or the program's span
says no `path`: a program that decodes every part twice."""


def read(ctx):
    parts = [sp["args"] for sp in ctx["spans"]
             if sp["name"] == "light.decode_part" and sp["ends_in_slice"] and "path" in sp["args"]]
    rows = sum(a.get("rows", 0) for a in parts)
    if not rows:
        return None
    return 100.0 * sum(a.get("rows", 0) for a in parts if a["path"] == "direct") / rows
