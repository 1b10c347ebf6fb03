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
  - running or waiting: on the enabled path a span() also carries
    `cpu_us`, its thread's CPU time inside it (time.thread_time_ns:
    Python and native code alike, GIL held or released), and
    `offcpu_us`, its duration less that, floored at 0: the thread was
    blocked (a lock, the GIL among them, or a wait by design) or
    runnable with no core. Where that clock is a dear call (a
    sandboxed kernel: measured when tracing is switched on) only the
    spans of _CPU_NAMED carry the two. A span entered while its
    thread's stack is empty also carries `runq_us`, the run-queue share
    of that, from the thread's /proc schedstat (left out where there is
    none). complete() and instant() carry none of the three. While tracing is
    on, a gc.callbacks hook records each collection as a `runtime.gc`
    span on the thread that ran it.

The span catalog, with every span's place and args, is
docs/observability.md's and is kept there alone.

Journey correlation: cross-node causality cannot use new_flow() ids
(process-private counters) or clock alignment (perf_counter epochs are
process-private). journey_key() derives a DETERMINISTIC id from
(height, round, msg kind, originator node id) — every node that
touches the same chain event computes the same key with no
coordination, so the lens merge layer (lens/traces.py) can draw
cross-node flow arrows from the keys alone.
"""

from __future__ import annotations

import gc
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
    "cpu_every_span": True,  # set_enabled(True) measures it
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
# atomic, but the lock also guards clear()/export() snapshots. It is
# re-entrant because a collection can start between two bytecodes of a
# thread that holds it, and the collection's own span lands here too.
_EVENTS: deque = deque(maxlen=_CAPACITY)
_LOCK = threading.RLock()
_FLOW_IDS = itertools.count(1)
_SPAN_IDS = itertools.count(1)  # args.span; 0 means "no span"
_LOCAL = threading.local()


def enabled() -> bool:
    return _STATE["on"]


# The spans that carry cpu_us where the thread's CPU clock is a dear
# call: the requests' roots, their one wait by design and the kernel
# dispatchers, which is what benchmark/window_spans.py reads.
_CPU_NAMED = frozenset({"light.update", "blocksync.try_sync", "verify.commit_collect",
                        "ops.verify_dispatch", "ops.msm_dispatch"})
_CPU_CLOCK_CHEAP_NS = 2000  # a plain Linux answers in 0.3-0.6 us, a gVisor sandbox in 6


def _cpu_clock_is_cheap() -> bool:
    """The best of four batches of eight reads: a thread switched out
    inside one batch does not make the clock dear."""
    best = None
    for _ in range(4):
        t0 = time.perf_counter_ns()
        for _ in range(8):
            time.thread_time_ns()
        took = time.perf_counter_ns() - t0
        best = took if best is None else min(best, took)
    return best / 8 < _CPU_CLOCK_CHEAP_NS


def set_enabled(on: bool) -> None:
    """Flip tracing at runtime (tests, bench stages, RPC debug). The
    collector's hook is in gc.callbacks only while tracing is on; which
    spans read the CPU clock is decided here, from what a read costs."""
    _STATE["on"] = on = bool(on)
    if on:
        _STATE["cpu_every_span"] = _cpu_clock_is_cheap()
    if on and _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    elif not on and _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def _on_gc(phase: str, info: dict) -> None:
    """gc.callbacks hook: one `runtime.gc` span a collection, on the
    thread that ran it, under whatever span is open there."""
    if phase == "start":
        _LOCAL.gc_t0 = _now_us()
        return
    t0 = getattr(_LOCAL, "gc_t0", None)
    if t0 is not None:
        _LOCAL.gc_t0 = None
        complete("runtime.gc", "runtime", t0, _now_us() - t0, generation=info["generation"],
                 collected=info["collected"], uncollectable=info["uncollectable"])


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


# The thread's own schedstat: "<on-cpu ns> <run-queue ns> <timeslices>".
_SCHEDSTAT = "/proc/self/task/%d/schedstat"


def _runq_ns() -> int | None:
    """Nanoseconds this thread has been runnable with no core, or None
    where the kernel keeps no such file. A descriptor opened on a
    thread's file stays that thread's, so each thread keeps its own;
    the file object closes it when the thread's locals go."""
    f = getattr(_LOCAL, "schedstat", None)
    if f is None:
        try:
            f = open(_SCHEDSTAT % threading.get_native_id(), "rb", buffering=0)
        except OSError:
            f = False
        _LOCAL.schedstat = f
    if f is False:
        return None
    try:
        return int(os.pread(f.fileno(), 64, 0).split()[1])
    except (OSError, IndexError, ValueError):
        return None


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
    __slots__ = ("name", "cat", "args", "id", "req", "_t0", "_c0", "_rq0", "_tid", "_tname")

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
        st = _stack()
        self._rq0 = None if st else _runq_ns()  # the thread's outermost span only
        st.append(self)
        clocked = _STATE["cpu_every_span"] or self.name in _CPU_NAMED
        self._t0 = _now_us()
        self._c0 = time.thread_time_ns() if clocked else None
        return self

    def __exit__(self, *exc):
        c1 = time.thread_time_ns() if self._c0 is not None else None
        t1 = _now_us()
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        dur = t1 - self._t0
        args = self.args
        if c1 is not None:
            cpu = args["cpu_us"] = (c1 - self._c0) / 1000.0
            args["offcpu_us"] = max(0.0, dur - cpu)
        if self._rq0 is not None:
            rq1 = _runq_ns()
            if rq1 is not None:
                args["runq_us"] = (rq1 - self._rq0) / 1000.0
        ev = {
            "name": self.name,
            "cat": self.cat or "tm",
            "ph": "X",
            "ts": self._t0,
            "dur": dur,
            "tid": self._tid,
            "tname": self._tname,
            "args": args,
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


if _STATE["on"]:  # TM_TPU_TRACE=1: enabled at import, the collector's hook with it
    set_enabled(True)
