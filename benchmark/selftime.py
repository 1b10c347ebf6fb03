"""A layer's self time: its span's duration less the part of that
interval its child spans cover (`choosing-metrics` section 4).

Children are looked for on the span's own thread: a span another thread
opened on its behalf (the engine's workers name the submitter's span as
their parent) runs beside it and takes nothing from it. Where the
program stamps `args.span` and `args.parent` the children are the spans
that name this one; a program older than the ids gives neither, and
then every span of the thread that lies inside this one counts, which
covers the same interval.
"""

from __future__ import annotations


def self_time_ns(span: dict, spans: list[dict]) -> float:
    """Nanoseconds of `span` ([t0, t1), ns) that no child on its thread
    covers. `spans` is `ctx["spans"]` (readers.py): the span itself may
    be among them."""
    t0, t1 = span["t0"], span["t1"]
    sid = span["args"].get("span")
    covered = []
    for sp in spans:
        if sp is span or sp["tid"] != span["tid"]:
            continue
        if sid is not None:
            if sp["args"].get("parent") != sid:
                continue
        elif sp["t0"] < t0 or sp["t1"] > t1 or (sp["t0"], sp["t1"]) == (t0, t1):
            continue
        s, e = max(sp["t0"], t0), min(sp["t1"], t1)
        if e > s:
            covered.append((s, e))
    busy, at = 0.0, t0
    for s, e in sorted(covered):
        if e > at:
            busy += e - max(s, at)
            at = e
    return (t1 - t0) - busy


def self_ms_per_op(ctx: dict, name: str, keep=None) -> float | None:
    """Self time of the spans called `name` (those `keep` accepts), in
    milliseconds per span that ended in the slice. None where none did."""
    total = n = 0
    for sp in ctx["spans"]:
        if sp["name"] == name and (keep is None or keep(sp)):
            total += self_time_ns(sp, ctx["spans"])
            n += sp["ends_in_slice"]
    return total / 1e6 / n if n else None
