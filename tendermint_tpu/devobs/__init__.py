"""tmdev: device-plane observatory (docs/observability.md#tmdev).

The rest of the observability stack (tmtrace/tmwatch/tmlens/tmpath)
is host-side: it sees the dispatch call and the collect return, and
nothing in between. What the device actually did — compiled a fresh
executable because the batch shape drifted, shipped megabytes over
the host<->device link, accumulated live buffers it never freed — is
invisible, which is exactly how the BENCH_r02/r03 runs died
undiagnosed. tmdev closes that gap with three feeds:

  compiles    a `jax.monitoring` duration listener captures every XLA
              backend compile (a persistent-cache load fires the same
              event, with the load time; the cache hit/miss events are
              tallied per attributed fn beside it). jax's monitoring events carry NO
              metadata (no fn, no shape), so attribution comes from a
              thread-local context the ops dispatch sites set around
              their kernel calls (`attribution(fn=..., rows=...)`) —
              backend compiles happen synchronously on the dispatching
              thread, so the context is live when the listener fires.
              Each compile lands in DeviceMetrics
              (`tendermint_device_compiles_total{fn}`,
              `..._bucket_compiles_total{fn,rows}` keyed on the
              engine's INTENDED pow2 batch bucket) and as a
              retrospective `device.compile` span in the Chrome trace,
              flow-linked to the launch it stalled.
  transfers   `transfer_span(dir, nbytes, flow=...)` wraps the h2d
              `jnp.asarray` block and the d2h `np.asarray` collect in
              ops/verify + ops/msm: `device_transfer_bytes_total{dir}`
              plus `device.h2d`/`device.d2h` span pairs whose flow
              arrows point at the launch they feed.
  residency   `sample_residency()` rides the FlightRecorder cadence
              (node/node.py passes it as a sampler): live-buffer
              bytes/count (`memory_stats()["bytes_in_use"]` when the
              backend exposes it, else the sum of `jax.live_arrays()`
              nbytes), per-cache-plane residency for the pk-cache and
              MSM table LRUs (read from the ops module globals WITHOUT
              constructing them), and a high-water mark. Because the
              recorder re-emits changed gauges into timeseries.jsonl,
              the residency timeline — and the device_mem_growth
              verdict built on it — survives SIGKILL.

Lifecycle: `maybe_install()` is env-gated (TM_TPU_DEVOBS=1, the
lockcheck/racecheck/byz pattern) and called by `cli.cmd_start` before
any node import; bench.py installs by default (BENCH_DEVOBS=off opts
out). `install()` NEVER raises: a missing jax, a missing
`jax.monitoring`, or a drifted listener API degrades to a warn-once
no-op — the import chain of a node must not depend on the
observability plane (tests/test_devobs.py pins this in a subprocess).
Disabled, nothing is registered and every hook is a dead bool check:
zero threads, zero listeners, zero cost. `uninstall()` prefers jax's
private unregister hooks and falls back to an inert flag the
callbacks consult first, so a jax without the private API still ends
up quiet.

The analysis side lives in lens/device.py (import-isolated: parses
persisted artifacts only, never imports this module or jax).
"""

from __future__ import annotations

import collections
import contextlib
import os
import sys
import threading
import time
import warnings

__all__ = [
    "attribution",
    "current_attribution",
    "enabled",
    "install",
    "maybe_install",
    "next_flow",
    "sample_residency",
    "status",
    "transfer_span",
    "uninstall",
]

# Cache planes the residency sampler reports, as (plane label, module
# holding the cache, module-global attribute). Read via sys.modules —
# the sampler must never IMPORT an ops module (that would build jit
# wrappers) nor construct a cache that dispatch hasn't.
_CACHE_PLANES = (
    ("ed25519_pk", "tendermint_tpu.ops.verify", "_PK_CACHE"),
    ("sr25519_pk", "tendermint_tpu.ops.verify_sr", "_SR_CACHE"),
)

# monitoring event suffixes -> compile-cache event label
_CACHE_EVENT_SUFFIXES = (
    "tasks_using_cache",
    "compile_requests_use_cache",
    "cache_hits",
    "cache_misses",
)

_LOCK = threading.Lock()
_STATE = {
    "installed": False,
    "warned": False,
    # plain counters mirrored from DeviceMetrics for the lock-free-ish
    # device_stats RPC snapshot (the FlightRecorder.tail() pattern: the
    # route reads a snapshot, never a live metrics object)
    "compiles": 0,
    "compile_seconds": 0.0,
    # persistent-compile-cache traffic by attributed fn: {fn: {event: n}}
    "cache_events": {},
    "transfers": {"h2d": 0, "d2h": 0},
    "transfer_bytes": {"h2d": 0, "d2h": 0},
    "residency_samples": 0,
    "live_buffer_bytes": 0,
    "high_water_bytes": 0,
}
# recent backend-compile events for the device_stats RPC tail
_COMPILE_TAIL: collections.deque = collections.deque(maxlen=256)
_TLS = threading.local()


def _warn_once(msg: str) -> None:
    with _LOCK:
        if _STATE["warned"]:
            return
        _STATE["warned"] = True
    warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _metrics():
    from ..metrics import device_metrics

    return device_metrics()


def enabled() -> bool:
    return _STATE["installed"]


def next_flow() -> int:
    """Allocate a trace flow id tying a launch span to the transfer
    and compile spans that fed it. Delegates to the trace ring's own
    allocator so devobs flows can never collide with engine flow ids
    (trace fid 0 is the no-arrow sentinel)."""
    from .. import trace as _trace

    return _trace.new_flow()


# ---------------------------------------------------------------- attribution


@contextlib.contextmanager
def attribution(**ctx):
    """Thread-local attribution context for the compile listener.
    Dispatch sites wrap their kernel call in
    `attribution(fn="bitmap", rows=512, flow=fid)`; a backend compile
    fired inside inherits those labels. Nested contexts merge (inner
    wins). No-cost no-op while devobs is disabled."""
    if not _STATE["installed"]:
        yield
        return
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    stack.append(ctx)
    try:
        yield
    finally:
        stack.pop()


def current_attribution() -> dict:
    merged: dict = {}
    for ctx in getattr(_TLS, "stack", ()) or ():
        merged.update(ctx)
    return merged


# ------------------------------------------------------------------ listeners


def _on_duration(event, duration=0.0, **kw):  # defensive signature
    """jax.monitoring duration listener. Must never raise — a broken
    observatory must not break a compile."""
    try:
        if not _STATE["installed"]:
            return
        name = str(event)
        if "backend_compile" not in name:
            return
        dur = float(duration or 0.0)
        ctx = current_attribution()
        fn = str(ctx.get("fn") or "unattributed")
        rows = ctx.get("rows")
        m = _metrics()
        m.compiles.add(1, fn)
        if rows is not None:
            m.bucket_compiles.add(1, fn, str(rows))
        m.compile_seconds.observe(dur)
        now = time.time()
        with _LOCK:
            _STATE["compiles"] += 1
            _STATE["compile_seconds"] += dur
            _COMPILE_TAIL.append({
                "t": round(now, 3),
                "fn": fn,
                "rows": rows,
                "dur_s": round(dur, 6),
            })
        from .. import trace as _trace

        dur_us = int(dur * 1e6)
        _trace.complete(
            "device.compile", "device",
            ts_us=_trace.now_us() - dur_us, dur_us=dur_us,
            fn=fn, rows=rows, flow=int(ctx.get("flow") or 0),
        )
    except Exception:  # noqa: BLE001 - observability never fails the host
        pass


def _on_event(event, **kw):  # defensive signature
    """jax.monitoring plain-event listener: compilation-cache traffic."""
    try:
        if not _STATE["installed"]:
            return
        name = str(event)
        for suffix in _CACHE_EVENT_SUFFIXES:
            if name.endswith(suffix):
                _metrics().compile_cache_events.add(1, suffix)
                fn = str(current_attribution().get("fn") or "unattributed")
                with _LOCK:
                    by_fn = _STATE["cache_events"].setdefault(fn, {})
                    by_fn[suffix] = by_fn.get(suffix, 0) + 1
                return
    except Exception:  # noqa: BLE001
        pass


# ------------------------------------------------------------------ transfers


@contextlib.contextmanager
def transfer_span(direction: str, nbytes: int, flow: int = 0):
    """Wrap one launch's h2d staging block or d2h collect: counts the
    bytes and emits a `device.h2d`/`device.d2h` span flow-linked to
    the launch. Plain passthrough while disabled."""
    if not _STATE["installed"]:
        yield
        return
    try:
        m = _metrics()
        m.transfer_bytes.add(int(nbytes), direction)
        m.transfers.add(1, direction)
        with _LOCK:
            _STATE["transfers"][direction] = _STATE["transfers"].get(direction, 0) + 1
            _STATE["transfer_bytes"][direction] = (
                _STATE["transfer_bytes"].get(direction, 0) + int(nbytes)
            )
        from .. import trace as _trace
    except Exception:  # noqa: BLE001
        yield
        return
    with _trace.span(f"device.{direction}", "device", bytes=int(nbytes), flow=int(flow)):
        yield


# ------------------------------------------------------------------ residency


def sample_residency() -> dict | None:
    """One HBM/live-buffer residency sample. Called on the flight-
    recorder cadence (node/node.py wires it as a sampler) and by the
    bench overhead stage. Returns the sample dict, or None when devobs
    is disabled or jax is unimportable. Never raises."""
    if not _STATE["installed"]:
        return None
    try:
        import jax

        m = _metrics()
        arrays = jax.live_arrays()
        count = len(arrays)
        total = None
        try:
            dev = jax.devices()[0]
            stats = dev.memory_stats()
            if stats and stats.get("bytes_in_use") is not None:
                total = int(stats["bytes_in_use"])
        except Exception:  # noqa: BLE001 - CPU backends return None
            total = None
        if total is None:
            total = sum(int(getattr(a, "nbytes", 0) or 0) for a in arrays)
        m.live_buffer_bytes.set(total)
        m.live_buffers.set(count)
        m.residency_samples.add(1)
        planes: dict = {}
        for plane, modname, attr in _CACHE_PLANES:
            mod = sys.modules.get(modname)
            cache = getattr(mod, attr, None) if mod is not None else None
            if cache is None:
                continue
            nbytes = 0
            for arr_attr in ("tables", "oks"):
                nbytes += int(getattr(getattr(cache, arr_attr, None), "nbytes", 0) or 0)
            entries = len(getattr(cache, "_lru", ()) or ())
            m.cache_resident_bytes.set(nbytes, plane)
            m.cache_resident_entries.set(entries, plane)
            planes[plane] = {"bytes": nbytes, "entries": entries}
        with _LOCK:
            _STATE["residency_samples"] += 1
            _STATE["live_buffer_bytes"] = total
            if total > _STATE["high_water_bytes"]:
                _STATE["high_water_bytes"] = total
            high = _STATE["high_water_bytes"]
        m.live_buffer_high_water.set(high)
        return {
            "live_buffer_bytes": total,
            "live_buffers": count,
            "high_water_bytes": high,
            "planes": planes,
        }
    except Exception:  # noqa: BLE001 - telemetry never fails the node
        return None


# ------------------------------------------------------------------ lifecycle


def install():
    """Register the monitoring listeners. Idempotent; NEVER raises.
    Returns True when the observatory is live, None when jax (or its
    monitoring API) is absent/drifted — with a one-time warning, so a
    node on a bare box boots clean instead of dying in telemetry."""
    with _LOCK:
        already = _STATE["installed"]
    if already:
        return True
    try:
        from jax import monitoring as _mon

        _mon.register_event_duration_secs_listener(_on_duration)
        _mon.register_event_listener(_on_event)
    except Exception as exc:  # noqa: BLE001 - degrade, never break the import chain
        _warn_once(
            f"devobs: jax.monitoring unavailable or drifted ({exc!r}); "
            "device observatory disabled"
        )
        return None
    with _LOCK:
        _STATE["installed"] = True
    # touch the metric families so an enabled run always exposes the
    # tendermint_device_* series, even before the first compile
    try:
        m = _metrics()
        m.transfer_bytes.add(0, "h2d")
        m.transfer_bytes.add(0, "d2h")
    except Exception:  # noqa: BLE001
        pass
    return True


def maybe_install():
    """TM_TPU_DEVOBS=1 gate (the lockcheck/racecheck/byz env pattern)."""
    if os.environ.get("TM_TPU_DEVOBS", "").strip().lower() not in (
        "1", "on", "true", "yes",
    ):
        return None
    return install()


def uninstall() -> None:
    """Unregister the listeners. jax has no public unregister, so this
    prefers the private by-callback hooks and falls back to flipping
    the inert flag both callbacks consult first — a jax without the
    private API still ends up quiet."""
    with _LOCK:
        if not _STATE["installed"]:
            return
        _STATE["installed"] = False
    try:
        from jax._src import monitoring as _prv

        _prv._unregister_event_duration_listener_by_callback(_on_duration)
        _prv._unregister_event_listener_by_callback(_on_event)
    except Exception:  # noqa: BLE001 - inert flag already covers it
        pass


def status(tail: int = 32) -> dict:
    """Snapshot for the device_stats RPC route: counters plus the
    recent compile-event tail, copied under the lock (the
    FlightRecorder.tail() pattern — the route never reaches into the
    metrics registry's locks)."""
    n = max(0, int(tail))
    with _LOCK:
        if not _STATE["installed"]:
            return {"enabled": False, "compiles": 0, "tail": []}
        recent = list(_COMPILE_TAIL)
        return {
            "enabled": True,
            "compiles": _STATE["compiles"],
            "compile_seconds": round(_STATE["compile_seconds"], 6),
            "cache_events": {fn: dict(ev) for fn, ev in _STATE["cache_events"].items()},
            "transfers": dict(_STATE["transfers"]),
            "transfer_bytes": dict(_STATE["transfer_bytes"]),
            "residency_samples": _STATE["residency_samples"],
            "live_buffer_bytes": _STATE["live_buffer_bytes"],
            "high_water_bytes": _STATE["high_water_bytes"],
            "tail": recent[len(recent) - min(n, len(recent)):],
        }
