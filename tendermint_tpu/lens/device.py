"""tmdev analysis plane: device digests and trip conditions.

Parses the device-plane evidence a run leaves behind — the
`tendermint_device_*` series in a node's final metrics.txt scrape and
the live-buffer residency timeline the flight recorder streamed into
timeseries.jsonl — into the per-node `device` / `device_memory`
blocks of fleet_report.json. The two trip conditions live here in ONE
copy each (the timeline_trips / journey_stall_offenders precedent),
shared by the gates (lens/gates.py `recompile_storm` /
`device_mem_growth`) and the `scripts/tmlens.py device` CLI, so the
two surfaces can never drift apart on identical evidence.

Import-isolated (check/rules.py `_ISOLATED_PREFIXES`): this module
reads persisted artifacts and parsed expositions only — it never
imports jax or the devobs runtime, so post-mortems run on bare CI
boxes with no accelerator stack.

  recompile_storm     a (fn, rows) cell of
                      `tendermint_device_bucket_compiles_total`
                      counted more than one compile. `rows` is the
                      dispatch site's INTENDED pow2 bucket
                      (ops/verify._pad_pow2), not the compiled shape —
                      so under shape churn every distinct raw batch
                      size lands a fresh compile on the SAME cell, and
                      count > 1 is direct evidence the engine's
                      shape-bucketing broke (the silent-throughput-
                      killer class; a test injects it by patching
                      ops/verify.pad_pow2_rows to leave rows unpadded).
  device_mem_growth   the trailing live-buffer residency samples are
                      monotone nondecreasing with total growth over a
                      floor — the buffer-leak signature, judged from
                      the streamed timeline so a SIGKILL'd node still
                      convicts.
"""

from __future__ import annotations

__all__ = [
    "LIVE_BUFFER_SERIES",
    "device_digest",
    "live_buffer_points",
    "mem_growth_offenders",
    "recompile_offenders",
]

NS = "tendermint"
LIVE_BUFFER_SERIES = f"{NS}_device_live_buffer_bytes"
# how many trailing residency points analyze_node persists per node —
# the ceiling on what the device_mem_growth gate can judge
MEMORY_TAIL_KEEP = 64


def device_digest(exp) -> dict | None:
    """Per-node `device` block from a parsed exposition (lens/prom.py
    Exposition). None when the scrape carries no tendermint_device_*
    series — devobs off is not evidence of anything."""
    compiles = list(exp.samples(f"{NS}_device_compiles_total"))
    transfers = list(exp.samples(f"{NS}_device_transfer_bytes_total"))
    live = exp.value(LIVE_BUFFER_SERIES)
    if not compiles and not transfers and live is None:
        return None
    compiles_by_fn = {}
    for labels, v in compiles:
        fn = labels.get("fn", "?")
        compiles_by_fn[fn] = compiles_by_fn.get(fn, 0) + int(v)
    cells = {}
    for labels, v in exp.samples(f"{NS}_device_bucket_compiles_total"):
        key = (labels.get("fn", "?"), labels.get("rows", "?"))
        cells[key] = cells.get(key, 0) + int(v)
    hist = exp.histogram(f"{NS}_device_compile_seconds")
    planes: dict = {}
    for labels, v in exp.samples(f"{NS}_device_cache_resident_bytes"):
        planes.setdefault(labels.get("plane", "?"), {})["bytes"] = int(v)
    for labels, v in exp.samples(f"{NS}_device_cache_resident_entries"):
        planes.setdefault(labels.get("plane", "?"), {})["entries"] = int(v)
    hw = exp.value(f"{NS}_device_live_buffer_high_water_bytes")
    return {
        "compiles": sum(compiles_by_fn.values()),
        "compiles_by_fn": compiles_by_fn,
        "bucket_compiles": [
            {"fn": fn, "rows": rows, "count": c}
            for (fn, rows), c in sorted(cells.items())
        ],
        "compile_seconds_total": round(hist.sum, 6) if hist else 0.0,
        "transfer_bytes": {
            labels.get("dir", "?"): int(v) for labels, v in transfers
        },
        "transfers": {
            labels.get("dir", "?"): int(v)
            for labels, v in exp.samples(f"{NS}_device_transfers_total")
        },
        "live_buffer_bytes": int(live) if live is not None else None,
        "high_water_bytes": int(hw) if hw is not None else None,
        "cache_planes": planes,
    }


def live_buffer_points(records) -> list[tuple[float, float]]:
    """[(t, bytes)] residency points from a parsed timeseries.jsonl
    record stream (lens/series.parse_timeseries). Sparse on purpose:
    the recorder only re-emits a gauge when it changed, and a leak
    changes it every tick."""
    from .series import reconstruct

    series, _marks = reconstruct(records, names={LIVE_BUFFER_SERIES})
    return series.get(LIVE_BUFFER_SERIES) or []


def recompile_offenders(node_digests, slack: int = 0) -> list[tuple]:
    """[(node, fn, rows, count)] bucket cells that compiled more than
    `1 + slack` times — the recompile_storm trip condition, ONE copy
    shared by the gate and the device CLI. `node_digests` is
    [(node_name, device_digest dict)]."""
    out = []
    for name, dev in node_digests:
        for cell in (dev or {}).get("bucket_compiles") or []:
            if cell.get("count", 0) > 1 + slack:
                out.append((name, cell.get("fn"), cell.get("rows"), cell["count"]))
    return out


def mem_growth_offenders(node_points, tail_points: int = 8,
                         min_growth_bytes: int = 1 << 20) -> list[tuple]:
    """[(node, growth_bytes, points)] nodes whose trailing
    `tail_points` residency samples never decreased and grew by at
    least `min_growth_bytes` total — the device_mem_growth trip
    condition, ONE copy shared by the gate and the device CLI.
    `node_points` is [(node_name, [(t, bytes), ...])]. Fewer than
    `tail_points` samples can't prove a leak (vacuous pass for that
    node): a monotone pair is noise, a monotone tail is a trend."""
    out = []
    for name, pts in node_points:
        vals = [float(v) for _t, v in pts][-int(tail_points):]
        if len(vals) < int(tail_points) or len(vals) < 2:
            continue
        deltas = [b - a for a, b in zip(vals, vals[1:])]
        growth = vals[-1] - vals[0]
        if all(d >= 0 for d in deltas) and growth >= float(min_growth_bytes):
            out.append((name, int(growth), len(vals)))
    return out
