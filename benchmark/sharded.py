"""What the readers of the engine's sharded route share: its launches,
rows and dispatch spans in the slice, and its program's device time.

The route launches one program, `jit_sharded_verify`
(`parallel/sharded_verify.py`), on every chip of the mesh at once; the
reducer averages a program's device time over the trace's device
planes, so `device_s` is one chip's share. All of it reads nothing on a
program without the route: no `path="sharded"` launch, no such program.
"""

from __future__ import annotations

from benchmark.readers import ENGINE, deltas, rows_by_path

PROGRAM = "sharded_verify"


def launches(ctx: dict) -> float:
    return sum(delta for labels, delta in deltas(ctx, ENGINE + "launches_total")
               if labels["path"] == "sharded")


def rows(ctx: dict) -> float:
    return rows_by_path(ctx).get("sharded", 0.0)


def device_s(ctx: dict) -> float:
    """Device seconds of the sharded program in the slice, a chip's mean."""
    if ctx["device"] is None:
        return 0.0
    return sum(s for name, s in ctx["device"]["ops"] if PROGRAM in name)


def dispatches(ctx: dict) -> list[dict]:
    """The route's `ops.verify_dispatch` spans that ended in the slice."""
    return [sp for sp in ctx["spans"] if sp["name"] == "ops.verify_dispatch"
            and sp["ends_in_slice"] and sp["args"].get("kernel") == "sharded"]
