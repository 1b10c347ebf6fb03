"""What a per-layer metric's reader is handed, and the arithmetic
readers share. A reader is `benchmark/metrics/<name>.py` with one
function `read(ctx) -> float | None`; `<name>` is the metric's name in
`BENCHMARK.json` up to its first dot, so `commit_walk_ms.sync` and
`commit_walk_ms.light` share `commit_walk_ms.py`. A reader that finds
nothing to read returns None and the harness leaves the metric out.

ctx keys (all of the traced slice unless said otherwise):
    spans      the program's and the benchmark's spans: {"name", "cat", "t0", "t1"
               (ns, clipped to the slice), "tid", "ends_in_slice", "args"}
    counters   {"before": {...}, "after": {...}}: engine metric samples keyed
               (series name, (label, value)...)
    devobs     {"window_start": status, "window_end": status}: tmdev's
               status() at the window's two ends
    device     the reducer's output for the slice, or None
    window     what the traffic's window() returned (the whole window)
    cutovers   {"device", "msm"}: the engine's cutovers in force
    peaks      the row of peaks.json for this device_kind
    work       benchmark/work.json: W, the multiply-adds of one verification
"""

from __future__ import annotations

ENGINE = "tendermint_engine_"


def span_ms(ctx: dict, *names: str) -> tuple[float, int]:
    """(milliseconds inside spans of these names, how many ended in the slice)."""
    total = n = 0
    for sp in ctx["spans"]:
        if sp["name"] in names:
            total += sp["t1"] - sp["t0"]
            n += sp["ends_in_slice"]
    return total / 1e6, n


def deltas(ctx: dict, series: str):
    """(labels, growth over the slice) of every sample of one series."""
    before = ctx["counters"]["before"]
    for (name, labels), after in ctx["counters"]["after"].items():
        if name == series:
            yield dict(labels), after - before.get((name, labels), 0.0)


def counter_delta(ctx: dict, series: str) -> float:
    return sum(delta for _, delta in deltas(ctx, series))


def rows_by_path(ctx: dict) -> dict[str, float]:
    out: dict[str, float] = {}
    for labels, delta in deltas(ctx, ENGINE + "path_rows_total"):
        out[labels["path"]] = out.get(labels["path"], 0.0) + delta
    return out


def device_rows(ctx: dict) -> float:
    return sum(v for path, v in rows_by_path(ctx).items() if "host" not in path)


def launches(ctx: dict) -> float:
    """Device launches the engine made in the slice (host-plane batches
    are counted under path="host" and left out)."""
    return sum(delta for labels, delta in deltas(ctx, ENGINE + "launches_total")
               if "host" not in labels["path"])
