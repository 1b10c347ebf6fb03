"""tmtrace — in-process block-lifecycle span tracing.

The verification engine (ops/engine.py) and the TPU dispatch path it
fronts are the hottest code in the repo, and their scheduling behavior
(coalescing, dispatch/collect overlap, host-vs-device path selection)
is invisible from aggregate metrics alone. This module records named
spans into a process-wide thread-safe ring buffer and exports them as
Chrome-trace / Perfetto JSON ("trace event format"), so one block's
wall-clock decomposes into spans across the consensus thread, the
engine workers, the host pool, and blocksync.

Design constraints:
  - near-zero overhead when DISABLED (the default): span() returns a
    shared no-op context manager after one dict lookup — no allocation,
    no clock read, no lock. TM_TPU_TRACE=1 enables at import;
    set_enabled() flips at runtime (tests, RPC).
  - thread-safe bounded memory: events land in a deque(maxlen=N)
    (TM_TPU_TRACE_BUF, default 65536) under a lock taken only on the
    ENABLED path, at span exit.
  - cross-thread correlation: spans accept a `flow` id (new_flow());
    the engine stamps each submitted job with one, so the caller's
    submit span, the dispatch worker's coalesce/launch spans, and the
    collect worker's demux span share it. Export adds Chrome-trace
    flow events (ph s/f) per flow id so Perfetto draws the arrows.
  - the span that caused it: on the enabled path every event (span,
    complete, instant) carries three ints in its args. `span` is its
    own id, from one process-wide counter; `parent` the id of the
    innermost span open on this thread when it started (0 for none);
    `req` the id of the root of that stack, inherited downwards, so
    the spans of one request share it. A span handed `parent=` and
    `req=` keeps them: that is how the engine's workers name the
    submitter's span on another thread (engine.dispatch,
    engine.host_verify and engine.collect take the oldest job's
    engine.submit span as parent, and list every job's req as `reqs`
    when a group was coalesced). A layer's self time is its span's
    duration less what the spans naming it as parent cover.

Span catalog (docs/observability.md): consensus.step (instant) /
consensus.finalize_commit, state.apply_block / state.validate_block /
state.finalize_block (state.commit_info under it: the last commit's
CommitInfo, `source` state or store) / state.abci_commit,
light.update / light.fetch / light.verify_step / light.header_checks /
light.detect_divergence / light.store (one light-client update, from
the caller to the store), verify.commit_walk (address lookup,
sign-bytes, tally) / verify.commit_dispatch / verify.commit_collect,
blocksync.try_sync (one block on the reactor's
thread) / blocksync.parts / blocksync.verify_commit /
blocksync.verify_ahead / blocksync.save_block / blocksync.apply /
blocksync.starved / blocksync.settle (both retrospective),
engine.submit / engine.coalesce / engine.dispatch /
engine.host_verify / engine.collect, ops.verify_dispatch /
ops.msm_dispatch with ops.prep / ops.rlc_scalars / ops.launch under
them, ops.pk_cache_lookup (the cached kernel's slot lookup) with
ops.pk_cache_fill (a miss's table build) under it, device.h2d (the
staging calls) /
device.wait (the collect thread blocked on the kernel) / device.d2h
(the read-back alone) / device.compile, sharded.verify,
mempool.admit_batch (coalesced tx admission: n/admitted/failed),
journey.proposal_build / journey.proposal / journey.block_assembled /
journey.quorum / journey.send / journey.recv (tmpath block-journey
plane, docs/observability.md#tmpath).

Journey correlation: cross-node causality cannot use new_flow() ids
(process-private counters) or clock alignment (perf_counter epochs are
process-private). journey_key() derives a DETERMINISTIC id from
(height, round, msg kind, originator node id) — every node that
touches the same chain event computes the same key with no
coordination, so the lens merge layer (lens/traces.py) can draw
cross-node flow arrows from the keys alone.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque

__all__ = [
    "enabled",
    "set_enabled",
    "span",
    "instant",
    "annotate",
    "new_flow",
    "journey_key",
    "now_us",
    "complete",
    "clear",
    "export",
    "export_json",
    "save",
]

_STATE = {
    "on": os.environ.get("TM_TPU_TRACE", "").strip().lower() in ("1", "on", "true", "yes"),
}
try:
    _CAPACITY = int(os.environ.get("TM_TPU_TRACE_BUF", "65536"))
    if _CAPACITY < 0:
        raise ValueError(_CAPACITY)
except ValueError:
    # forgiving like TM_TPU_TRACE itself: a malformed observability
    # knob must not stop the node from importing/booting
    _CAPACITY = 65536

# Ring of finished events. Each entry is a dict already shaped like a
# Chrome-trace event minus pid (stamped at export). deque.append is
# atomic, but the lock also guards clear()/export() snapshots.
_EVENTS: deque = deque(maxlen=_CAPACITY)
_LOCK = threading.Lock()
_FLOW_IDS = itertools.count(1)
_SPAN_IDS = itertools.count(1)  # args.span; 0 means "no span"
_LOCAL = threading.local()


def enabled() -> bool:
    return _STATE["on"]


def set_enabled(on: bool) -> None:
    """Flip tracing at runtime (tests, bench stages, RPC debug)."""
    _STATE["on"] = bool(on)


def new_flow() -> int:
    """Fresh correlation id for spans that cross threads."""
    return next(_FLOW_IDS)


def journey_key(height: int, round_: int, kind: str, origin: str = "") -> str:
    """Deterministic cross-node journey id for one chain event: every
    node derives the same key from (height, round, kind, originator
    node id) with no clock alignment or coordination. `origin` is the
    node id of whichever node ORIGINATED the event (frame sender,
    proposer); pass "" for events whose identity is already unique per
    (height, round, kind) — e.g. quorum assembly, finalize — so all
    nodes share one key. Spans/instants carry it as args.journey; the
    lens merge layer groups on it to draw cross-node arrows."""
    return f"{int(height)}/{int(round_)}/{kind}@{(origin or '-')[:16]}"


def _now_us() -> float:
    return time.perf_counter_ns() / 1000.0


def now_us() -> float:
    """Current trace-clock timestamp (µs). Callers that need to emit a
    RETROSPECTIVE span (see complete()) capture this at the event's
    start — e.g. the first vote of a (height, round, type) — and emit
    once the end is known."""
    return _now_us()


def _stack() -> list:
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = _LOCAL.stack = []
    return st


def _stamp(args: dict) -> int:
    """Give an event's args its own id, its parent's and its
    request's (enabled path only). `parent` and `req` already in args
    were handed across threads by the caller and stay."""
    sid = args["span"] = next(_SPAN_IDS)
    st = _stack()
    top = st[-1] if st else None
    if "parent" not in args:
        args["parent"] = top.id if top is not None else 0
    if "req" not in args:
        args["req"] = top.req if top is not None else sid
    return sid


class _NoopSpan:
    """Shared disabled-path span: no state, no clock, no lock, no id
    drawn (id and req read 0, the "no span" sentinel)."""

    __slots__ = ()
    id = req = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def annotate(self, **kv):
        pass


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("name", "cat", "args", "id", "req", "_t0", "_tid", "_tname")

    def __init__(self, name: str, cat: str, args: dict):
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        t = threading.current_thread()
        self._tid = t.ident or 0
        self._tname = t.name
        self.id = _stamp(self.args)
        self.req = self.args["req"]
        _stack().append(self)
        self._t0 = _now_us()
        return self

    def __exit__(self, *exc):
        t1 = _now_us()
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        ev = {
            "name": self.name,
            "cat": self.cat or "tm",
            "ph": "X",
            "ts": self._t0,
            "dur": t1 - self._t0,
            "tid": self._tid,
            "tname": self._tname,
            "args": self.args,
        }
        with _LOCK:
            _EVENTS.append(ev)
        return False

    def annotate(self, **kv):
        self.args.update(kv)


def span(name: str, cat: str = "", **args):
    """Context manager recording one complete ("X") event. Disabled
    path returns the shared no-op after a single dict lookup. Its
    parent is the innermost span open on this thread, unless `parent=`
    and `req=` name a span of another thread (see the module doc)."""
    if not _STATE["on"]:
        return _NOOP
    return _Span(name, cat, args)


def annotate(**kv) -> None:
    """Attach args to the innermost open span on THIS thread."""
    if not _STATE["on"]:
        return
    st = _stack()
    if st:
        st[-1].args.update(kv)


def instant(name: str, cat: str = "", **args) -> None:
    """One instant ("i") event — step transitions, demux wakeups."""
    if not _STATE["on"]:
        return
    t = threading.current_thread()
    _stamp(args)
    ev = {
        "name": name,
        "cat": cat or "tm",
        "ph": "i",
        "s": "t",  # thread-scoped instant
        "ts": _now_us(),
        "tid": t.ident or 0,
        "tname": t.name,
        "args": args,
    }
    with _LOCK:
        _EVENTS.append(ev)


def complete(name: str, cat: str, ts_us: float, dur_us: float, **args) -> None:
    """One complete ("X") event with EXPLICIT timestamps — for spans
    whose start is only recognized in hindsight (quorum assembly: the
    first vote's arrival becomes the span start once 2/3 is reached;
    part reassembly: the first part's arrival once the set completes).
    `ts_us` must come from now_us() so the event shares the ring's
    clock. Its parent is the span open on this thread when it is
    emitted, which is where the hindsight was had."""
    if not _STATE["on"]:
        return
    t = threading.current_thread()
    _stamp(args)
    ev = {
        "name": name,
        "cat": cat or "tm",
        "ph": "X",
        "ts": ts_us,
        "dur": max(0.0, dur_us),
        "tid": t.ident or 0,
        "tname": t.name,
        "args": args,
    }
    with _LOCK:
        _EVENTS.append(ev)


def clear() -> None:
    with _LOCK:
        _EVENTS.clear()


def export() -> dict:
    """Snapshot the ring as a Chrome-trace JSON object (the
    `traceEvents` array format Perfetto and chrome://tracing open
    directly). Thread-name metadata events and per-flow s/f arrows are
    synthesized here so the hot path never pays for them."""
    pid = os.getpid()
    with _LOCK:
        events = list(_EVENTS)
    out = []
    tnames: dict[int, str] = {}
    flows: dict[int, list] = {}
    for ev in events:
        e = dict(ev)
        tname = e.pop("tname", None)
        if tname and e["tid"] not in tnames:
            tnames[e["tid"]] = tname
        e["pid"] = pid
        # fid 0 is the "tracing was off at submit" sentinel (jobs in
        # flight across a live-enable): never synthesize arrows for it —
        # it would draw one false causality chain across unrelated spans
        fid = (e.get("args") or {}).get("flow")
        if fid and e["ph"] == "X":
            flows.setdefault(fid, []).append(e)
        out.append(e)
    for tid, name in tnames.items():
        out.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": name},
        })
    # Flow arrows: one s at the first span's start, one f at the last
    # span's end, binding the enclosing slices (bp: "e").
    for fid, evs in flows.items():
        if len(evs) < 2:
            continue
        evs.sort(key=lambda e: e["ts"])
        first, last = evs[0], evs[-1]
        out.append({
            "name": "flow", "cat": "tm.flow", "ph": "s", "id": fid,
            "pid": pid, "tid": first["tid"], "ts": first["ts"],
        })
        out.append({
            "name": "flow", "cat": "tm.flow", "ph": "f", "bp": "e", "id": fid,
            "pid": pid, "tid": last["tid"], "ts": last["ts"] + last.get("dur", 0),
        })
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def export_json() -> str:
    return json.dumps(export())


def save(path: str) -> int:
    """Write the Chrome-trace JSON to path; returns the event count."""
    doc = export()
    with open(path, "w") as f:
        json.dump(doc, f)
    return len(doc["traceEvents"])
