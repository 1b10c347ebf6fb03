"""Traffic generators, one per kind of client. A traffic mix is a data
file under `benchmark/traffic/` whose `driver` names a module here; the
harness imports it by that name and knows nothing else about it.

A driver module has `class Traffic` with:
    __init__(config, params, seed)   the cell's configuration and the mix's parameters
    build()                          data from the seed (set-up)
    warm_up()                        every shape and one whole pass of what the window repeats
    window(seconds) -> dict          the measured window: {"ops", "window_s", "latencies_ms", ...}
    check() -> (checks, attempted, failed)   after the window: every number compared,
                                     beside its limit
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass
class Check:
    """One number compared when `correct` is decided: `value` may not
    exceed `limit`. An exact comparison counts what differs: limit 0."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit
