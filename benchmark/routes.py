"""What the readers of the engine's routes share: the per-signature
(bitmap) route's part of the slice, and the autotune probe's prices.

The bitmap route launches the per-signature programs (`verify_kernel*`
and, on a cache miss, `build_pk_tables*`); the other device route is
the MSM (`msm_verify_kernel*`). The device trace names programs, not
routes, so the bitmap route's device time is every program of the slice
but the MSM's and the harness's anchor.
"""

from __future__ import annotations

from benchmark.readers import ENGINE, deltas, rows_by_path
from benchmark.reducer import ANCHOR


def bitmap_rows(ctx: dict) -> float:
    return rows_by_path(ctx).get("bitmap", 0.0)


def bitmap_launches(ctx: dict) -> float:
    return sum(delta for labels, delta in deltas(ctx, ENGINE + "launches_total")
               if labels["path"] == "bitmap")


def bitmap_device_s(ctx: dict) -> float | None:
    """Device seconds of the per-signature programs in the slice, None
    where there is no device plane or none of them ran."""
    if ctx["device"] is None:
        return None
    seconds = sum(s for name, s in ctx["device"]["ops"]
                  if "msm" not in name and ANCHOR not in name)
    return seconds or None


def gauge(ctx: dict, series: str) -> float | None:
    """A gauge of the engine at the slice's end, None where the program
    has no such series or never set it."""
    for (name, _labels), value in ctx["counters"]["after"].items():
        if name == ENGINE + series:
            return value
    return None
