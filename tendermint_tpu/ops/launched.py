"""The row buckets each signature plane's device programs have run at in
this process.

Every launch of a plane's program notes its padded row count here where
it is made (ops/verify.py, ops/verify_sr.py, ops/msm.py, the pubkey
cache's fill): a caller's own warm-up that calls a kernel directly counts
as much as a batch the engine routed. The engine (ops/engine.py
`_take_group`) reads it before it joins two jobs that were queued apart:
a group at a bucket no launch of its plane has used would load a program
while the jobs wait, which costs seconds where the two launched apart
cost milliseconds. Imports no jax, as the engine does not.
"""

from __future__ import annotations

_ROWS: dict[str, set[int]] = {}


def pad_pow2(n: int, floor: int = 8) -> int:
    """The bucket a launch of n rows runs at: the next power of two, at
    least `floor` (ops/verify.py pads every plane's batches to it)."""
    size = floor
    while size < n:
        size *= 2
    return size


def note(plane: str, rows: int) -> None:
    """A program of `plane` ran at `rows` padded rows."""
    _ROWS.setdefault(plane, set()).add(rows)


def used(plane: str, rows: int) -> bool:
    """Whether a program of `plane` has run at `rows` padded rows."""
    return rows in _ROWS.get(plane, ())
