"""tmtrace — the in-process span tracer (tendermint_tpu/trace/).

Covers the PR-4 tentpole surface: enable/disable semantics, the
Chrome-trace JSON export schema (what Perfetto/chrome://tracing
require to open the file), cross-thread flow correlation, the ring
bound, and the disabled-path overhead guard (the tracer rides the
engine hot path, so "off" must stay free).
"""

from __future__ import annotations

import gc
import hashlib
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from tendermint_tpu import trace as T


@pytest.fixture(autouse=True)
def _reset_tracer():
    was = T.enabled()
    T.set_enabled(False)
    T.clear()
    yield
    T.set_enabled(was)
    T.clear()


IDS = ("span", "parent", "req")
CLOCKS = ("cpu_us", "offcpu_us", "runq_us")


def _own(args: dict) -> dict:
    """An event's args without the ids every enabled event carries and
    the clocks every span does."""
    return {k: v for k, v in args.items() if k not in IDS + CLOCKS}


def test_disabled_records_nothing():
    assert not T.enabled()
    with T.span("x", "test", a=1):
        pass
    T.instant("i")
    T.complete("c", "test", T.now_us(), 1.0)
    T.annotate(b=2)
    assert T.export()["traceEvents"] == []


def test_span_records_complete_event():
    T.set_enabled(True)
    with T.span("work", "test", rows=7) as sp:
        time.sleep(0.002)
        sp.annotate(extra="y")
    doc = T.export()
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(evs) == 1
    ev = evs[0]
    assert ev["name"] == "work" and ev["cat"] == "test"
    assert ev["dur"] >= 2000  # microseconds
    assert _own(ev["args"]) == {"rows": 7, "extra": "y"}


def test_annotate_targets_innermost_open_span():
    T.set_enabled(True)
    with T.span("outer"):
        with T.span("inner"):
            T.annotate(who="inner")
        T.annotate(who="outer")
    by_name = {e["name"]: e for e in T.export()["traceEvents"] if e.get("ph") == "X"}
    assert _own(by_name["inner"]["args"]) == {"who": "inner"}
    assert _own(by_name["outer"]["args"]) == {"who": "outer"}


def test_chrome_trace_schema():
    """The export must be a valid trace-event-format object: a
    traceEvents array where every event carries name/ph/pid/tid, X
    events carry ts+dur, instants carry a scope, and thread_name
    metadata binds the tids."""
    T.set_enabled(True)
    with T.span("a", "s", flow=T.new_flow()):
        pass
    T.instant("blip", "s")
    doc = json.loads(T.export_json())  # round-trips as strict JSON
    assert isinstance(doc["traceEvents"], list)
    assert doc["displayTimeUnit"] in ("ms", "ns")
    phs = set()
    for ev in doc["traceEvents"]:
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ph"] in ("X", "i", "M", "s", "f")
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        phs.add(ev["ph"])
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
        if ev["ph"] == "i":
            assert ev["s"] in ("t", "p", "g")
        if ev["ph"] in ("X", "i"):
            assert all(isinstance(ev["args"][k], int) for k in IDS)
        if ev["ph"] in ("s", "f"):
            assert "id" in ev and "ts" in ev
    assert {"X", "i", "M"} <= phs
    names = [e["args"]["name"] for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(names), "thread_name metadata missing"


def test_flow_arrows_span_threads():
    T.set_enabled(True)
    fid = T.new_flow()

    def worker():
        with T.span("collect", "test", flow=fid):
            pass

    with T.span("submit", "test", flow=fid):
        pass
    t = threading.Thread(target=worker, name="flow-worker")
    t.start()
    t.join()
    evs = T.export()["traceEvents"]
    arrows = [e for e in evs if e["ph"] in ("s", "f") and e.get("id") == fid]
    assert {e["ph"] for e in arrows} == {"s", "f"}
    xtids = {e["tid"] for e in evs if e.get("ph") == "X"}
    assert len(xtids) == 2, "spans should land on two distinct threads"
    # the s arrow starts on the earlier span's thread, f ends on the later
    s_ev = next(e for e in arrows if e["ph"] == "s")
    f_ev = next(e for e in arrows if e["ph"] == "f")
    assert s_ev["ts"] <= f_ev["ts"]


def test_ring_buffer_bounds_memory():
    T.set_enabled(True)
    cap = T._EVENTS.maxlen
    for i in range(cap + 100):
        T.instant(f"e{i}")
    evs = [e for e in T.export()["traceEvents"] if e["ph"] != "M"]
    assert len(evs) == cap  # a collection's span takes a place in the ring like any other
    # oldest events were dropped, newest survive
    assert [e for e in evs if e["ph"] == "i"][-1]["name"] == f"e{cap + 99}"


def test_save_writes_loadable_json(tmp_path):
    T.set_enabled(True)
    with T.span("persisted"):
        pass
    path = str(tmp_path / "out.trace.json")
    n = T.save(path)
    with open(path) as f:
        doc = json.load(f)
    assert n == len(doc["traceEvents"]) >= 1
    assert any(e["name"] == "persisted" for e in doc["traceEvents"])


def test_concurrent_spans_all_recorded():
    T.set_enabled(True)
    n_threads, per = 8, 200

    def worker(k):
        for i in range(per):
            with T.span(f"t{k}", "mt", i=i):
                pass

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = [e for e in T.export()["traceEvents"] if e.get("ph") == "X" and e["cat"] == "mt"]
    assert len(evs) == n_threads * per


def test_disabled_overhead_guard():
    """The disabled span() path must stay near-free: one dict lookup
    and a shared no-op context manager — no allocation, clock read, or
    lock. Budget is generous (shared CI box) but still catches an
    accidental hot-path regression (e.g. allocating a Span or reading
    the clock while disabled) which lands >10x over it."""
    assert not T.enabled()
    n = 200_000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            with T.span("hot", "guard", rows=1):
                pass
        best = min(best, time.perf_counter() - t0)
    per_call_us = best / n * 1e6
    assert per_call_us < 5.0, f"disabled span() costs {per_call_us:.2f}us/call"
    assert T.export()["traceEvents"] == []


def test_flow_zero_sentinel_gets_no_arrows():
    """flow=0 marks 'tracing was off at submit' (jobs in flight across
    a live enable): export must not group those spans into a fake flow
    or draw arrows between unrelated work."""
    T.set_enabled(True)
    with T.span("a", "t", flow=0):
        pass
    with T.span("b", "t", flow=0):
        pass
    evs = T.export()["traceEvents"]
    assert not [e for e in evs if e["ph"] in ("s", "f")]


# ------------------------------------------------- running or waiting


def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _hash_8mb() -> None:
    hashlib.sha256(bytes(8 << 20)).digest()  # hashlib releases the GIL above 2 KiB


@pytest.mark.parametrize("work,cpu_share", [
    (lambda: _spin(0.2), (0.8, 1.01)),  # computing: CPU time is the wall's, a shared box's cut off
    (lambda: time.sleep(0.2), (0.0, 0.1)),  # a wait: nearly none
    (_hash_8mb, (0.8, 1.01)),  # native code with the GIL released is CPU time too
], ids=["spins", "sleeps", "native_without_the_gil"])
def test_a_span_splits_its_duration_into_cpu_and_off_cpu(work, cpu_share):
    T.set_enabled(True)
    for _ in range(5):  # another tenant's burst on a shared core is not the clock's doing
        T.clear()
        with T.span("work", "t"):
            work()
        (ev,) = _events(("X",))
        dur, args = ev["dur"], ev["args"]
        assert args["offcpu_us"] == pytest.approx(max(0.0, dur - args["cpu_us"]), abs=1e-6)
        if cpu_share[0] <= args["cpu_us"] / dur <= cpu_share[1]:
            return
    pytest.fail(f"cpu_us {args['cpu_us']:.0f} of dur {dur:.0f}: not within {cpu_share}")


def test_where_the_cpu_clock_is_dear_only_the_named_spans_read_it(monkeypatch):
    """A sandboxed kernel answers `thread_time_ns` in 6 us: the
    price is measured when tracing is switched on, and then only the
    spans the readers need carry the two args."""
    T.set_enabled(True)
    assert T._STATE["cpu_every_span"]  # a plain Linux: every span
    monkeypatch.setattr(T, "_CPU_CLOCK_CHEAP_NS", 0)
    T.set_enabled(True)
    assert not T._STATE["cpu_every_span"]
    with T.span("blocksync.try_sync", "t"):
        with T.span("blocksync.apply", "t"):
            with T.span("verify.commit_collect", "t"):
                pass
    by = {e["name"]: e["args"] for e in _events(("X",))}
    assert {"cpu_us", "offcpu_us"} <= set(by["blocksync.try_sync"])
    assert {"cpu_us", "offcpu_us"} <= set(by["verify.commit_collect"])
    assert not {"cpu_us", "offcpu_us"} & set(by["blocksync.apply"])
    assert set(T._CPU_NAMED) == {"light.update", "blocksync.try_sync", "verify.commit_collect",
                                 "ops.verify_dispatch", "ops.msm_dispatch"}
    monkeypatch.undo()
    T.set_enabled(True)
    assert T._STATE["cpu_every_span"]


def test_complete_and_instant_carry_no_clock_of_the_thread():
    T.set_enabled(True)
    T.instant("tick", "t")
    T.complete("hindsight", "t", T.now_us() - 5.0, 5.0)
    assert all(not set(CLOCKS) & set(e["args"]) for e in _events())


def _nested_on(results: dict, key: str) -> None:
    with T.span(key + ".outer", "t"):
        with T.span(key + ".inner", "t"):
            _spin(0.01)
    results[key] = threading.get_native_id()


def test_runq_us_is_on_a_threads_outermost_span_and_is_that_threads_own():
    T.set_enabled(True)
    if T._runq_ns() is None:
        pytest.skip("no /proc schedstat on this kernel")
    native = {}
    _nested_on(native, "main")
    t = threading.Thread(target=_nested_on, args=(native, "second"))
    t.start()
    t.join(timeout=10)
    assert not t.is_alive() and native["second"] != native["main"]
    by = {e["name"]: e["args"] for e in _events(("X",))}
    for key in ("main", "second"):
        assert by[key + ".outer"]["runq_us"] >= 0.0 and "runq_us" not in by[key + ".inner"]
    # each thread read its own file: the second thread's is not the first's descriptor
    assert T._LOCAL.schedstat.name == T._SCHEDSTAT % native["main"]
    # a root handed another thread's parent is still its own thread's outermost
    with T.span("handed", "t", parent=7, req=3):
        pass
    assert "runq_us" in _events(("X",))[-1]["args"]


def test_runq_us_is_left_out_where_the_kernel_keeps_no_schedstat(monkeypatch, tmp_path):
    monkeypatch.setattr(T, "_SCHEDSTAT", str(tmp_path / "none-%d"))
    seen = {}

    def worker():  # a fresh thread: no descriptor opened before the patch
        with T.span("outer", "t") as sp:
            seen.update(sp.args)

    T.set_enabled(True)
    t = threading.Thread(target=worker)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    (ev,) = _events(("X",))
    assert "runq_us" not in ev["args"] and ev["args"]["cpu_us"] >= 0.0


def test_a_collection_is_a_span_under_the_open_span():
    before = list(gc.callbacks)
    T.set_enabled(True)
    T.set_enabled(True)  # installed once
    assert len(gc.callbacks) == len(before) + 1
    with T.span("holder", "t") as holder:
        gc.collect()
    T.set_enabled(False)
    assert gc.callbacks == before
    gc.collect()  # tracing off: no span, no callback
    (ev,) = [e for e in _events(("X",)) if e["name"] == "runtime.gc"]
    assert ev["cat"] == "runtime" and ev["dur"] > 0
    assert ev["args"]["parent"] == holder.id and ev["args"]["req"] == holder.req
    assert ev["args"]["generation"] == 2
    assert {"collected", "uncollectable"} <= set(ev["args"])
    assert ev["tid"] == threading.get_ident()


def test_a_process_that_never_enabled_tracing_carries_no_gc_callback():
    code = ("import gc; from tendermint_tpu import trace; "
            "assert not trace.enabled() and gc.callbacks == [], gc.callbacks")
    env = {k: v for k, v in os.environ.items() if k != "TM_TPU_TRACE"}
    subprocess.run([sys.executable, "-c", code], check=True, env=env, timeout=60)
    env["TM_TPU_TRACE"] = "1"
    code = ("import gc; from tendermint_tpu import trace; "
            "assert trace.enabled() and trace._on_gc in gc.callbacks")
    subprocess.run([sys.executable, "-c", code], check=True, env=env, timeout=60)


# ------------------------------------------- the span that caused it


def _events(ph=("X", "i")) -> list[dict]:
    return [e for e in T.export()["traceEvents"] if e.get("ph") in ph]


def _ctx_spans() -> list[dict]:
    """The ring's X events in the shape the benchmark's readers get
    (benchmark/readers.py), so that benchmark/selftime.py can be asked."""
    return [{"name": e["name"], "cat": e["cat"], "tid": e["tid"], "t0": e["ts"] * 1e3,
             "t1": (e["ts"] + e["dur"]) * 1e3, "ends_in_slice": True, "args": e["args"]}
            for e in _events(("X",))]


def _covered_share(spans: list[dict], root_name: str, keep=None) -> float:
    """The part of a span called `root_name` that its children on the
    same thread cover: the median over those spans (a thread switched
    out between two children is the box's doing, not the catalogue's)."""
    import statistics

    from benchmark.selftime import self_time_ns

    roots = [sp for sp in spans if sp["name"] == root_name and (keep is None or keep(sp))]
    assert roots, f"no {root_name} span among {sorted({sp['name'] for sp in spans})}"
    return statistics.median(1.0 - self_time_ns(sp, spans) / (sp["t1"] - sp["t0"])
                             for sp in roots)


def test_nested_spans_carry_span_parent_and_req():
    T.set_enabled(True)
    with T.span("root", "t") as root:
        with T.span("child", "t") as child:
            with T.span("grandchild", "t"):
                pass
        with T.span("sibling", "t"):
            pass
    with T.span("next_root", "t"):
        pass
    by = {e["name"]: e["args"] for e in _events()}
    assert len({a["span"] for a in by.values()}) == 5 and all(a["span"] > 0 for a in by.values())
    assert by["root"]["parent"] == 0 and by["root"]["req"] == by["root"]["span"] == root.id
    assert by["child"]["parent"] == root.id and by["sibling"]["parent"] == root.id
    assert by["grandchild"]["parent"] == child.id == by["child"]["span"]
    assert {by[n]["req"] for n in ("root", "child", "grandchild", "sibling")} == {root.id}
    assert by["next_root"]["parent"] == 0 and by["next_root"]["req"] == by["next_root"]["span"]
    # another thread's spans do not inherit this thread's stack
    seen = {}

    def worker():
        with T.span("elsewhere", "t") as sp:
            seen["parent"], seen["req"], seen["id"] = sp.args["parent"], sp.req, sp.id

    with T.span("holder", "t"):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10)
    assert seen["parent"] == 0 and seen["req"] == seen["id"]


def test_complete_and_instant_under_an_open_span():
    T.set_enabled(True)
    with T.span("outer", "t") as outer:
        T.instant("tick", "t", n=1)
        T.complete("hindsight", "t", T.now_us() - 50.0, 50.0, what="x")
    T.instant("alone", "t")
    T.complete("alone_too", "t", T.now_us(), 1.0)
    by = {e["name"]: e["args"] for e in _events()}
    for name in ("tick", "hindsight"):
        assert by[name]["parent"] == outer.id and by[name]["req"] == outer.req
        assert by[name]["span"] not in (0, outer.id)
    assert _own(by["tick"]) == {"n": 1} and _own(by["hindsight"]) == {"what": "x"}
    for name in ("alone", "alone_too"):
        assert by[name]["parent"] == 0 and by[name]["req"] == by[name]["span"] > 0
    # a span handed a parent and a request of another thread keeps them
    with T.span("handed", "t", parent=7, req=3) as sp:
        with T.span("below", "t"):
            pass
    by = {e["name"]: e["args"] for e in _events()}
    assert (by["handed"]["parent"], by["handed"]["req"]) == (7, 3)
    assert by["below"]["parent"] == sp.id and by["below"]["req"] == 3


def test_disabled_draws_no_id():
    assert not T.enabled()
    before = next(T._SPAN_IDS)
    with T.span("x", "t", a=1) as sp:
        T.instant("i", "t")
        T.complete("c", "t", 0.0, 1.0)
    assert sp is T._NOOP and (sp.id, sp.req) == (0, 0)
    assert next(T._SPAN_IDS) == before + 1
    assert T.export()["traceEvents"] == []


def _signed_jobs(n: int, tag: bytes):
    from helpers import make_keys

    keys = make_keys(n)
    msgs = [tag + bytes([i]) for i in range(n)]
    return [k.pub_key().bytes() for k in keys], msgs, [k.sign(m) for k, m in zip(keys, msgs)]


def test_engine_workers_name_the_submitters_span(monkeypatch):
    """A submit on this thread, engine.dispatch and engine.collect on
    the workers: their parent is the oldest job's engine.submit span,
    their req its request, and a coalesced group lists every request
    it served. What the workers open below inherits through their own
    stacks."""
    from tendermint_tpu.ops import engine as E

    eng = E.get_engine()
    assert all(eng.submit("ed25519", *_signed_jobs(2, b"warm")).result(timeout=300))
    # Hold the dispatch worker inside the first group's dispatch while
    # two more callers submit: they are then taken as one group.
    held, release = threading.Event(), threading.Event()
    autotune = E.maybe_autotune

    def gate():
        if not held.is_set():
            held.set()
            assert release.wait(timeout=60)
        return autotune()

    monkeypatch.setattr(E, "maybe_autotune", gate)
    T.set_enabled(True)
    T.clear()
    try:
        first = eng.submit("ed25519", *_signed_jobs(2, b"a"))
        assert held.wait(timeout=60)
        with T.span("caller.b", "test") as b:
            hb = eng.submit("ed25519", *_signed_jobs(2, b"b"))
        with T.span("caller.c", "test") as c:
            hc = eng.submit("ed25519", *_signed_jobs(3, b"c"))
    finally:
        release.set()
    for h in (first, hb, hc):
        assert all(h.result(timeout=300))
    time.sleep(0.05)  # the collect worker closes its span after waking the callers
    events = _events(("X",))
    submits = [e["args"] for e in events if e["name"] == "engine.submit"]
    assert [a["parent"] for a in submits[1:]] == [b.id, c.id]
    assert [a["req"] for a in submits[1:]] == [b.req, c.req]
    this_thread = threading.get_ident()
    for name in ("engine.dispatch", "engine.collect"):
        alone, group = [e for e in events if e["name"] == name]
        assert alone["tid"] != this_thread and group["tid"] != this_thread
        assert alone["args"]["parent"] == submits[0]["span"]
        assert alone["args"]["req"] == submits[0]["req"] and "reqs" not in alone["args"]
        assert group["args"]["jobs"] == 2 and group["args"]["rows"] == 5
        assert group["args"]["parent"] == submits[1]["span"]  # the oldest job's
        assert group["args"]["req"] == b.req
        assert group["args"]["reqs"] == [b.req, c.req]
    # below the workers' spans: the host pool's or the ops' spans carry the request on
    dispatch_ids = {e["args"]["span"] for e in events if e["name"] == "engine.dispatch"}
    below = [e for e in events if e["name"] in ("engine.host_verify", "ops.verify_dispatch",
                                                "ops.msm_dispatch")]
    assert below and all(e["args"]["req"] in (submits[0]["req"], b.req) for e in below)
    assert all(e["args"]["parent"] in dispatch_ids | {s["span"] for s in submits}
               for e in below)
    assert all(set(IDS) <= set(e["args"]) for e in _events())


# ------------------------------------ the catalogue's spans, where the work is


@pytest.fixture(scope="module")
def tiny_chain():
    """Eight blocks of eight validators: every commit batch is over the
    tests' device cutover, so the walk, the launch and the wait all run."""
    from tendermint_tpu.blocksync import fixture

    return fixture.build_chain(seed=25, n_vals=8, n_blocks=8, txs_per_block=2,
                               chain_id="trace-chain")


@pytest.fixture
def observed(monkeypatch):
    """The device route for every batch (the engine's cutover at two
    rows), with the observatory installed: device.h2d and device.d2h
    are its spans."""
    from tendermint_tpu import devobs
    from tendermint_tpu.crypto import ed25519 as ed

    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", 2)
    was = devobs.enabled()
    devobs.install()
    yield
    if not was:
        devobs.uninstall()


def _light_client(chain):
    from tendermint_tpu.light import LightClient, LocalProvider, TrustOptions
    from tendermint_tpu.utils.tmtime import Time

    def provider(name):
        return LocalProvider(chain.chain_id, chain.block_store, chain.state_store, name)

    now = Time.from_unix_ns(provider("p").light_block(0).signed_header.header.time.unix_ns()
                            + 10**9)
    return LightClient(
        chain.chain_id,
        TrustOptions(period_ns=3600 * 10**9, height=1, hash=chain.block_hashes[0]),
        provider("primary"), [provider("witness")], clock=lambda: now)


def test_a_light_update_is_covered_by_its_spans(tiny_chain, observed):
    _light_client(tiny_chain).verify_light_block_at_height(4)  # every program loaded
    T.set_enabled(True)
    T.clear()
    client = _light_client(tiny_chain)
    client.verify_light_block_at_height(5)
    assert client.update().height == tiny_chain.height
    spans = _ctx_spans()
    roots = [sp for sp in spans if sp["name"] == "light.update"]
    assert [sp["args"]["mode"] for sp in roots] == ["root", "skipping", "skipping"]
    assert [sp["args"]["height"] for sp in roots] == [1, 5, tiny_chain.height]
    assert all(sp["args"]["parent"] == 0 for sp in roots)
    names = {sp["name"] for sp in spans}
    assert {"light.update", "light.fetch", "light.verify_step", "light.header_checks",
            "light.detect_divergence", "light.store", "verify.commit_walk",
            "verify.commit_dispatch", "verify.commit_collect"} <= names, names
    steps = [sp["args"] for sp in spans if sp["name"] == "light.verify_step"]
    assert [(a["from"], a["to"], a["adjacent"], a["outcome"]) for a in steps] == [
        (1, 5, False, "ok"), (5, tiny_chain.height, False, "ok")]
    fetches = [sp["args"]["provider"] for sp in spans if sp["name"] == "light.fetch"]
    assert fetches.count("witness") == 2 and fetches.count("primary") >= 3
    divergence = [sp["args"] for sp in spans if sp["name"] == "light.detect_divergence"]
    assert all(a["witnesses"] == 1 and a["cross_referenced"] == 1 for a in divergence)
    # one request, one id, down to the engine's workers on their threads
    update = roots[1]
    same_req = {sp["name"] for sp in spans if sp["args"]["req"] == update["args"]["req"]}
    assert {"engine.dispatch", "ops.prep", "ops.launch", "device.h2d", "engine.collect",
            "device.wait", "device.d2h"} <= same_req, same_req
    assert _covered_share(spans, "light.update") >= 0.9


def test_a_refused_step_shows_what_refused_it(tiny_chain, monkeypatch):
    """light/verifier.py wraps whatever a commit check raised in
    ErrInvalidHeader, a fault of the device plane too: the step's span
    says `invalid` and names the exception it was."""
    from tendermint_tpu.light import verifier as vf

    client = _light_client(tiny_chain)

    def device_fault(*a, **kw):
        raise RuntimeError("device plane down")

    monkeypatch.setattr(vf, "verify_commit_light_after_trusting", device_fault)
    T.set_enabled(True)
    T.clear()
    with pytest.raises(vf.ErrInvalidHeader):
        client.verify_light_block_at_height(4)
    step = next(e["args"] for e in _events(("X",)) if e["name"] == "light.verify_step")
    assert (step["outcome"], step["error"]) == ("invalid", "RuntimeError")
    assert client.store.light_block(4) is None


def test_a_batched_commit_check_is_covered_by_its_spans(tiny_chain, observed):
    from tendermint_tpu.types.validation import verify_commit_light

    lb = _light_client(tiny_chain).primary.light_block(3)
    header, commit = lb.signed_header.header, lb.signed_header.commit

    def check():
        verify_commit_light(tiny_chain.chain_id, lb.validator_set, commit.block_id,
                            header.height, commit)

    check()
    T.set_enabled(True)
    T.clear()
    with T.span("test.commit", "test"):
        check()
    spans = _ctx_spans()
    walk = next(sp["args"] for sp in spans if sp["name"] == "verify.commit_walk")
    # the light rule stops once more than 2/3 of 8 equal validators are tallied
    assert (walk["height"], walk["nsigs"], walk["walked"]) == (3, 6, 6)
    root = next(sp for sp in spans if sp["name"] == "test.commit")
    children = [sp["name"] for sp in sorted(spans, key=lambda sp: sp["t0"])
                if sp["args"]["parent"] == root["args"]["span"]]
    assert children == ["verify.commit_walk", "verify.commit_dispatch", "verify.commit_collect"]
    assert _covered_share(spans, "test.commit") >= 0.9


def test_an_rlc_launch_is_covered_by_its_spans(observed):
    from tendermint_tpu.ops import msm

    pks, msgs, sigs = _signed_jobs(8, b"rlc")
    assert msm.verify_batch_rlc(pks, msgs, sigs) is True  # the program loaded
    T.set_enabled(True)
    T.clear()
    for _ in range(5):
        assert msm.verify_batch_rlc(pks, msgs, sigs) is True
    spans = _ctx_spans()
    by = {sp["name"]: sp for sp in spans}  # the last launch's
    dispatch = by["ops.msm_dispatch"]["args"]["span"]
    for name in ("ops.prep", "ops.rlc_scalars", "ops.launch"):
        assert by[name]["args"]["parent"] == dispatch and by[name]["args"]["rows"] == 8
    assert by["ops.launch"]["args"]["padded"] == 8
    assert by["device.h2d"]["args"]["parent"] == by["ops.launch"]["args"]["span"]
    # the wait for the kernel, then the read-back alone
    assert by["device.wait"]["t1"] <= by["device.d2h"]["t0"]
    assert by["device.d2h"]["args"]["bytes"] == 1
    assert _covered_share(spans, "ops.msm_dispatch") >= 0.9


def test_a_short_blocksync_is_covered_by_its_spans(tiny_chain, observed):
    from tendermint_tpu.blocksync import fixture

    T.set_enabled(True)
    T.clear()
    result = fixture.sync(tiny_chain, timeout=300.0)
    assert result.caught_up and result.fatal is None and not result.peer_errors
    spans = _ctx_spans()
    names = {sp["name"] for sp in spans}
    assert {"blocksync.try_sync", "blocksync.parts", "blocksync.verify_commit",
            "blocksync.verify_ahead", "blocksync.save_block", "blocksync.apply",
            "blocksync.starved", "blocksync.settle", "verify.commit_walk",
            "device.wait"} <= names, names
    applied = [sp for sp in spans if sp["name"] == "blocksync.try_sync"
               and sp["args"].get("applied")]
    assert [sp["args"]["height"] for sp in applied] == list(range(1, tiny_chain.height))
    assert all(sp["args"]["parent"] == 0 and sp["args"]["req"] == sp["args"]["span"]
               for sp in applied)
    polls = [sp for sp in spans if sp["name"] == "blocksync.try_sync"
             and not sp["args"]["applied"]]
    starved = [sp for sp in spans if sp["name"] == "blocksync.starved"]
    assert polls and sum(sp["args"]["polls"] for sp in starved) <= len(polls)
    settle = next(sp for sp in spans if sp["name"] == "blocksync.settle")
    assert settle["args"]["seconds"] == 1.0 and settle["t1"] - settle["t0"] == pytest.approx(1e9)
    # a block's verify-ahead runs under the block before it
    ahead = next(sp for sp in spans if sp["name"] == "blocksync.verify_ahead")
    assert ahead["args"]["parent"] in {sp["args"]["span"] for sp in applied}
    assert _covered_share(spans, "blocksync.try_sync",
                          lambda sp: sp["args"].get("applied")) >= 0.9
