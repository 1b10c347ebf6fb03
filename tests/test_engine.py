"""Unified async verification engine (ops/engine.py).

Pins the tentpole contracts: coalescing with per-caller demux
(mixed-validity batches stay isolated per caller), worker exception
propagation (a dispatch-stage failure reaches the submitting caller and
the engine keeps serving), byte-identical acceptance with the engine
off (direct dispatch) and on, autotune leaving the CPU defaults
untouched, and the msm tail-row alignment assertion (ADVICE r5 medium).
Includes the tier-1 bench smoke that pushes one tiny coalesced batch
through the engine under JAX_PLATFORMS=cpu so the path cannot rot
between TPU windows.
"""

import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(__file__))

import tendermint_tpu.crypto.ed25519 as ed
from tendermint_tpu.crypto import ed25519_ref as ref
from tendermint_tpu.crypto.ed25519 import Ed25519BatchVerifier, Ed25519PubKey
from tendermint_tpu.ops import engine as E

from test_batch_verify import make_jobs


def submit_and_wait(pks, msgs, sigs):
    return E.get_engine().submit("ed25519", pks, msgs, sigs).result(timeout=120)


# ------------------------------------------------------------- coalescing


def test_take_group_coalesces_same_plane_in_order():
    """The group former merges every queued same-plane job (bounded by
    MAX_COALESCE_ROWS) and leaves other planes queued, preserving
    order — the demux contract depends on this exact layout."""
    eng = E.VerifyEngine()
    jobs = [
        E._Job("ed25519", [b"a"], [b"m"], [b"s"]),
        E._Job("sr25519", [b"b"], [b"m"], [b"s"]),
        E._Job("ed25519", [b"c"] * 3, [b"m"] * 3, [b"s"] * 3),
    ]
    eng._pending = list(jobs)
    group = eng._take_group()
    assert group == [jobs[0], jobs[2]]
    assert eng._pending == [jobs[1]]


def test_take_group_respects_row_cap(monkeypatch):
    monkeypatch.setattr(E, "MAX_COALESCE_ROWS", 4)
    eng = E.VerifyEngine()
    jobs = [E._Job("ed25519", [b"x"] * 3, [b"m"] * 3, [b"s"] * 3) for _ in range(3)]
    eng._pending = list(jobs)
    group = eng._take_group()
    assert group == [jobs[0]]  # 3 + 3 > 4: second job waits
    assert eng._pending == [jobs[1], jobs[2]]


def test_engine_demux_mixed_validity_host_path():
    """One caller's bitmap through the engine host plane: per-row
    validity demuxed exactly, matching the oracle."""
    pks, msgs, sigs = make_jobs(7, tamper_idx={1, 4})
    bools = submit_and_wait(pks, msgs, sigs)
    assert bools == [i not in {1, 4} for i in range(7)]


def test_engine_concurrent_caller_isolation():
    """Concurrent callers coalesce into shared launches; each must get
    back exactly its own rows — an invalid signature in one caller's
    batch must not leak into any other caller's verdict."""
    n_callers = 4
    results: dict[int, list[bool]] = {}
    jobs = {}
    for c in range(n_callers):
        tamper = {2} if c == 1 else set()
        jobs[c] = make_jobs(5 + c, tamper_idx=tamper)
    barrier = threading.Barrier(n_callers)

    def caller(c):
        barrier.wait()
        results[c] = submit_and_wait(*jobs[c])

    threads = [threading.Thread(target=caller, args=(c,)) for c in range(n_callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for c in range(n_callers):
        want = [True] * (5 + c)
        if c == 1:
            want[2] = False
        assert results[c] == want, c


def test_engine_device_path_matches_direct(monkeypatch):
    """Engine-on and engine-off (direct dispatch) must return
    byte-identical (ok, bools) on the same mixed-validity corpus, on
    both the host plane and the device plane (cutover forced down)."""
    corpus = [
        make_jobs(6),
        make_jobs(8, tamper_idx={0, 7}),
        make_jobs(5, tamper_idx={2}),
    ]

    def run(pks, msgs, sigs):
        bv = Ed25519BatchVerifier()
        for p, m, s in zip(pks, msgs, sigs):
            bv.add(Ed25519PubKey(p), m, s)
        return bv.verify()

    for force_device in (False, True):
        if force_device:
            monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", 4)
            monkeypatch.setattr(ed, "MSM_BATCH_CUTOVER", 4)
        got_on = []
        monkeypatch.setenv("TM_TPU_ENGINE", "auto")
        for pks, msgs, sigs in corpus:
            got_on.append(run(pks, msgs, sigs))
        monkeypatch.setenv("TM_TPU_ENGINE", "off")
        got_off = [run(pks, msgs, sigs) for pks, msgs, sigs in corpus]
        assert got_on == got_off
        for (ok, bools), (pks, msgs, sigs) in zip(got_on, corpus):
            want = [ref.verify(p, m, s, zip215=True) for p, m, s in zip(pks, msgs, sigs)]
            assert bools == want
            assert ok == all(want)


def test_engine_zip215_edge_acceptance():
    """The engine host plane must keep ZIP-215 acceptance exactly: the
    OpenSSL C loop only ever pre-accepts, the oracle decides rejects."""
    pks, msgs, sigs = make_jobs(2)
    # small-order pubkey, identity R, s = 0: cofactored-valid, rejected
    # by OpenSSL's cofactorless check — must come back True via oracle
    so = ref.small_order_points()[1]
    pks.append(so)
    msgs.append(b"anything")
    sigs.append(ref.compress(ref.IDENTITY) + b"\x00" * 32)
    # s >= L: invalid everywhere
    s = int.from_bytes(sigs[0][32:], "little")
    pks.append(pks[0])
    msgs.append(msgs[0])
    sigs.append(sigs[0][:32] + int.to_bytes(s + ref.L, 32, "little"))
    bools = submit_and_wait(pks, msgs, sigs)
    assert bools == [True, True, True, False]


def test_engine_empty_and_unknown_plane():
    h = E.get_engine().submit("ed25519", [], [], [])
    assert h.result(timeout=5) == []
    with pytest.raises(ValueError):
        E.get_engine().submit("secp256k1", [b"x"], [b"m"], [b"s"])


def test_engine_ragged_batch_rejected():
    """Mismatched pks/msgs/sigs lengths must raise at submit — a
    silent zip() truncation would report unverified tail rows as
    accepted and shift later coalesced callers' demux slices."""
    pks, msgs, sigs = make_jobs(3)
    with pytest.raises(ValueError, match="ragged batch"):
        E.get_engine().submit("ed25519", pks[:2], msgs, sigs)
    with pytest.raises(ValueError, match="ragged batch"):
        E.get_engine().submit("ed25519", pks, msgs[:2], sigs)


# ------------------------------------------------- exception propagation


def test_engine_worker_exception_propagates_and_engine_survives(monkeypatch):
    """A failure inside the dispatch worker (here: _use_device blowing
    up during batch classification) must surface from THIS caller's
    result() — and the workers must keep serving later submissions."""
    boom = RuntimeError("prep thread exploded")

    def explode():
        raise boom

    pks, msgs, sigs = make_jobs(3)
    monkeypatch.setattr(ed, "_use_device", explode)
    handle = E.get_engine().submit("ed25519", pks, msgs, sigs)
    with pytest.raises(RuntimeError, match="prep thread exploded"):
        handle.result(timeout=120)
    monkeypatch.undo()
    # engine still alive and correct after the failure
    assert submit_and_wait(pks, msgs, sigs) == [True, True, True]


def test_engine_collect_exception_propagates(monkeypatch):
    """A failure in the collect stage (host verify itself) also reaches
    the caller instead of wedging the pipeline."""
    def bad_host(pks, msgs, sigs):
        raise ValueError("host plane exploded")

    monkeypatch.setitem(E._HOST_VERIFY, "ed25519", bad_host)
    pks, msgs, sigs = make_jobs(2)
    handle = E.get_engine().submit("ed25519", pks, msgs, sigs)
    with pytest.raises(ValueError, match="host plane exploded"):
        handle.result(timeout=120)
    monkeypatch.undo()
    assert submit_and_wait(pks, msgs, sigs) == [True, True]


def test_engine_short_result_fails_group(monkeypatch):
    """A verify path returning fewer results than rows must fail the
    group loudly — a silent slice-truncation would wake callers with
    empty results and all([]) == True reports forged rows as accepted."""
    monkeypatch.setitem(E._HOST_VERIFY, "ed25519", lambda pks, msgs, sigs: [])
    pks, msgs, sigs = make_jobs(2)
    handle = E.get_engine().submit("ed25519", pks, msgs, sigs)
    with pytest.raises(RuntimeError, match="returned 0 results for 2 rows"):
        handle.result(timeout=120)
    # non-sized result (None) must also fail the group, not the worker
    monkeypatch.setitem(E._HOST_VERIFY, "ed25519", lambda pks, msgs, sigs: None)
    handle = E.get_engine().submit("ed25519", pks, msgs, sigs)
    with pytest.raises(TypeError):
        handle.result(timeout=120)
    monkeypatch.undo()
    assert submit_and_wait(pks, msgs, sigs) == [True, True]


# ------------------------------------------------------------- autotune


def test_autotune_keeps_defaults_without_accelerator(monkeypatch):
    """On CPU-only runs the microprobe must not fire: the documented
    defaults stay (deterministic tests, no surprise compiles)."""
    monkeypatch.setitem(E._AUTOTUNE, "done", False)
    before = (ed.DEVICE_BATCH_CUTOVER, ed.MSM_BATCH_CUTOVER)
    E.maybe_autotune()
    assert (ed.DEVICE_BATCH_CUTOVER, ed.MSM_BATCH_CUTOVER) == before
    assert E._AUTOTUNE["done"] is True


def test_autotune_off_env_disables_probe(monkeypatch):
    monkeypatch.setitem(E._AUTOTUNE, "done", False)
    monkeypatch.setenv("TM_TPU_AUTOTUNE", "off")
    calls = []
    monkeypatch.setattr(ed, "_accelerator_present", lambda: calls.append(1) or True)
    E.maybe_autotune()
    assert not calls  # off: never even probes for an accelerator


def _unpinned_probe(monkeypatch):
    """A process with a (faked) accelerator, no cutover pinned, and the
    probe not yet run; monkeypatch restores the cutovers afterwards."""
    monkeypatch.setitem(E._AUTOTUNE, "done", False)
    for var in ("TM_TPU_AUTOTUNE", "TM_TPU_BATCH_CUTOVER", "TM_TPU_MSM_CUTOVER"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(ed, "_accelerator_present", lambda: True)
    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", 64)
    monkeypatch.setattr(ed, "MSM_BATCH_CUTOVER", 256)


def _probe_prices(monkeypatch, launch: float) -> None:
    """The probe's clock and the three things it times, faked: a host
    verification takes 1 unit, the tiny launch `launch` units, the host
    route's 64-row batch 3.2."""
    import types

    from tendermint_tpu.ops import verify as V

    clock = [0.0]

    def tick(dt, result=True):
        def fake(*a, **k):
            clock[0] += dt
            return result
        return fake

    monkeypatch.setattr(ed, "_single_verify", tick(1.0))
    monkeypatch.setattr(V, "verify_batch", tick(launch))
    monkeypatch.setitem(E._HOST_VERIFY, "ed25519", tick(3.2, [True] * 64))
    monkeypatch.setattr(E, "_time", types.SimpleNamespace(
        perf_counter=lambda: clock[0], monotonic=time.monotonic))


def test_autotune_probe_failure_is_counted_not_swallowed(monkeypatch):
    """A probe launch that fails on the device leaves the defaults in
    force, once, and says so: logged and counted."""
    from tendermint_tpu.metrics import engine_metrics
    from tendermint_tpu.ops import verify as V

    _unpinned_probe(monkeypatch)

    def unavailable(*a):
        raise RuntimeError("UNAVAILABLE: TPU backend setup/compile error")

    monkeypatch.setattr(V, "verify_batch", unavailable)
    failures = _counter_value(engine_metrics().autotune_failures)
    E.maybe_autotune()
    assert (ed.DEVICE_BATCH_CUTOVER, ed.MSM_BATCH_CUTOVER) == (64, 256)
    assert _counter_value(engine_metrics().autotune_failures) == failures + 1
    assert E._AUTOTUNE["done"] is True
    E.maybe_autotune()  # one shot: no second attempt
    assert _counter_value(engine_metrics().autotune_failures) == failures + 1


def test_autotune_finishes_before_the_first_batch_is_routed(monkeypatch):
    """The probe runs on the dispatch worker ahead of the first group:
    submit() does not wait for it, and no batch is routed under
    cutovers that are about to change."""
    _unpinned_probe(monkeypatch)
    # 1 time unit per host verify, 20 per tiny launch: the launch pays
    # for itself at 32 rows (8, 16 < 20 <= 32); the MSM cutover is the
    # table's, and this device kind has no entry: the default stays
    _probe_prices(monkeypatch, launch=20.0)
    seen = []
    real = E.VerifyEngine._dispatch_group

    def spy(self, group, seq=0):
        seen.append((ed.DEVICE_BATCH_CUTOVER, ed.MSM_BATCH_CUTOVER, E._AUTOTUNE["done"]))
        return real(self, group, seq)

    monkeypatch.setattr(E.VerifyEngine, "_dispatch_group", spy)
    monkeypatch.setitem(E._HOST_VERIFY, "ed25519", lambda pks, msgs, sigs: [True] * len(sigs))
    handle = E.get_engine().submit("ed25519", [b"k" * 32] * 2, [b"m"] * 2, [b"s" * 64] * 2)
    assert handle.result(timeout=60) == [True, True]
    assert seen == [(32, 256, True)]


# Device kind -> its entry in the routing cases: the chip the table's
# entry was measured on, a kind given a 512-row entry for the test, and
# one the table has never heard of (crypto/ed25519.py's default stays).
_ENTRIES = {"TPU v5 lite": E.MSM_CUTOVER_ROWS["TPU v5 lite"], "crossing at 512": 512,
            "unheard of": None}


@pytest.mark.parametrize("kind,rows", [
    (kind, rows) for kind in _ENTRIES for rows in (51, 101, 334, 667, 1000)
] + [("crossing at 512", 256), ("crossing at 512", 257), ("crossing at 512", 512),
     ("crossing at 512", 2048), ("TPU v5 lite", 8192), ("unheard of", 256)])
def test_a_device_batch_takes_the_program_the_table_says(monkeypatch, kind, rows):
    """The choice between the two device programs is the measured
    crossover of the device kind in use, by the batch's padded size:
    the probe draws the device cutover as it did and leaves the MSM
    cutover to MSM_CUTOVER_ROWS; a kind without an entry keeps the
    module default. The kernels are stubbed: the route is under test."""
    from tendermint_tpu.metrics import engine_metrics
    from tendermint_tpu.ops import msm as M
    from tendermint_tpu.ops import verify as V

    _unpinned_probe(monkeypatch)
    monkeypatch.setattr(E, "_device_kind", lambda: kind)
    entry = _ENTRIES[kind]
    if entry is None:
        want_msm = rows >= 256
    else:
        monkeypatch.setitem(E.MSM_CUTOVER_ROWS, kind, entry)
        want_msm = V._pad_pow2(rows) >= entry
    want = "two_phase_msm" if want_msm else "bitmap"
    _probe_prices(monkeypatch, launch=8.0)  # what 8 host verifications cost: a device cutover of 8
    monkeypatch.setattr(V, "verify_batch_cached_async", lambda pks, msgs, sigs: len(sigs))
    monkeypatch.setattr(V, "collect", lambda n: [True] * n)
    monkeypatch.setattr(M, "verify_batch_rlc_async", lambda pks, msgs, sigs: len(sigs))
    monkeypatch.setattr(M, "collect_rlc", lambda n: True)

    def path_rows():
        return {labels["path"]: v for _, labels, v in engine_metrics().path_rows.samples()
                if labels["plane"] == "ed25519" and labels["status"] == "accept"}

    before = path_rows()
    handle = E.get_engine().submit("ed25519", [b"k" * 32] * rows, [b"m"] * rows,
                                   [b"s" * 64] * rows)
    assert handle.result(timeout=60) == [True] * rows
    grown = {p: v - before.get(p, 0.0) for p, v in path_rows().items() if v != before.get(p, 0.0)}
    assert grown == {want: rows}
    assert ed.DEVICE_BATCH_CUTOVER == 8
    assert isinstance(ed.MSM_BATCH_CUTOVER, int)
    assert (ed.MSM_BATCH_CUTOVER == 256) == (entry is None)
    # the direct-dispatch copies compare the same number the same way
    assert (rows >= ed.MSM_BATCH_CUTOVER) == want_msm


# ------------------------------------------- ADVICE r5 regression pins


def test_msm_misaligned_batch_raises_not_truncates(monkeypatch):
    """ADVICE r5 (medium): a batch size not divisible by the stream
    count must raise at trace time, not silently drop tail rows from
    the RLC sum (a dropped row holding the only invalid signature would
    falsely accept the batch)."""
    import numpy as np

    from tendermint_tpu.ops import msm as M

    monkeypatch.setattr(M, "G_STREAMS", 8)
    a = np.zeros((12, 32), np.uint8)
    r = np.zeros((12, 32), np.uint8)
    zk = np.zeros((12, 32), np.uint8)
    z = np.zeros((12, 16), np.uint8)
    zs = np.zeros((1, 32), np.uint8)
    with pytest.raises(ValueError, match="not a multiple of the stream count"):
        M.msm_verify_kernel_impl(a, r, zk, z, zs)


def test_msm_cached_precheck_refusal_never_touches_cache():
    """ADVICE r5 (low): a batch refused at precheck (malformed row)
    must not insert anything into the HBM pubkey cache — malformed
    pubkeys must not evict live validator keys."""
    import secrets

    from tendermint_tpu.ops import msm as M
    from tendermint_tpu.ops.verify import pubkey_cache

    pks, msgs, sigs = make_jobs(3)
    fresh = ref.gen_privkey(secrets.token_bytes(32))[32:]
    pks.append(fresh)
    msgs.append(b"m")
    sigs.append(b"\x00" * 10)  # malformed: fails precheck
    cache = pubkey_cache()
    before = dict(cache._lru)
    assert M.verify_batch_rlc_cached_async(pks, msgs, sigs) is None
    assert dict(cache._lru) == before  # no insertions, no reordering
    assert fresh not in cache._lru


def test_rlc_cached_overflow_fallback_reuses_prep(monkeypatch):
    """When the batch holds more distinct keys than the HBM cache, the
    cached RLC dispatch must fall back to the uncached kernel WITHOUT
    re-running prepare_batch, and still verify both polarities."""
    from tendermint_tpu.ops import msm as M
    from tendermint_tpu.ops import verify as V

    cache = V.PubkeyCache(
        capacity=2, build_fn=V.build_pk_tables_split,
        entry_shape=(V.PK_SPLITS, 16, 4, 32),
    )
    monkeypatch.setattr(V, "_PK_CACHE", cache)
    calls = []
    real_prepare = M.prepare_batch

    def counting_prepare(*a):
        calls.append(1)
        return real_prepare(*a)

    monkeypatch.setattr(M, "prepare_batch", counting_prepare)
    pks, msgs, sigs = make_jobs(4)  # 4 distinct keys > capacity 2
    z = bytes(range(1, 17)) * 4
    assert M.collect_rlc(M.verify_batch_rlc_cached_async(pks, msgs, sigs, z_raw=z)) is True
    assert len(calls) == 1, "fallback re-ran prepare_batch"
    pks2, msgs2, sigs2 = make_jobs(4, tamper_idx={1})
    assert M.collect_rlc(M.verify_batch_rlc_cached_async(pks2, msgs2, sigs2, z_raw=z)) is False


def test_rlc_precheck_refusal_dispatches_bitmap_immediately(monkeypatch):
    """ADVICE r5 (low): when the RLC dispatch refuses at precheck, the
    bitmap kernel must be dispatched at verify_async time (launch-now/
    collect-later preserved), not deferred to completion."""
    monkeypatch.setenv("TM_TPU_ENGINE", "off")
    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", 4)
    monkeypatch.setattr(ed, "MSM_BATCH_CUTOVER", 4)
    from tendermint_tpu.ops import verify as V

    dispatched_at = []
    real = V.verify_batch_cached_async

    def spy(*a, **k):
        dispatched_at.append("dispatch")
        return real(*a, **k)

    monkeypatch.setattr(V, "verify_batch_cached_async", spy)
    pks, msgs, sigs = make_jobs(5)
    # s >= L: well-formed 64 bytes (passes add()) but fails the RLC
    # precheck, so _dispatch_rlc returns None
    s = int.from_bytes(sigs[2][32:], "little")
    sigs[2] = sigs[2][:32] + int.to_bytes(s + ref.L, 32, "little")
    bv = Ed25519BatchVerifier()
    for p, m, s in zip(pks, msgs, sigs):
        bv.add(Ed25519PubKey(p), m, s)
    pending = bv.verify_async()
    assert dispatched_at == ["dispatch"], "bitmap not dispatched at verify_async time"
    ok, bools = pending()
    assert ok is False
    assert bools == [True, True, False, True, True]


# ------------------------------------------------------- bench smoke


def test_bench_coalesced_smoke():
    """Tier-1 smoke for the bench engine stage: one tiny coalesced
    round through bench.bench_coalesced under JAX_PLATFORMS=cpu — the
    exact code path the driver-time bench runs, so it cannot silently
    rot between TPU windows."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        import bench
    finally:
        sys.path.remove(root)
    pks, msgs, sigs = make_jobs(6)
    rate = bench.bench_coalesced((pks, msgs, sigs), n_callers=3, per_call=2, iters=2)
    assert rate > 0


# ---------------------------------------------------------- observability


def _counter_value(metric) -> float:
    return sum(v for _, _, v in metric.samples())


def test_engine_trace_and_telemetry_integration(monkeypatch):
    """PR-4 acceptance: a multi-caller verify workload with TM_TPU_TRACE
    on yields Chrome-trace spans covering submit -> coalesce -> dispatch
    -> collect, flow-correlated across threads, with NONZERO
    dispatch/collect overlap accounted; and the engine series (queue
    depth, coalesce factor, launch latency, per-path counters) land on
    the process-global registry."""
    import time as _t

    from tendermint_tpu import trace as T
    from tendermint_tpu.metrics import engine_metrics, global_registry

    if not E.engine_enabled():
        pytest.skip("TM_TPU_ENGINE=off")
    m = engine_metrics()
    overlap_before = _counter_value(m.overlap_seconds)
    launches_before = _counter_value(m.launches)

    # Slow the host verify a little so consecutive coalesced batches
    # PIPELINE: batch B's host_verify/dispatch runs while batch A's
    # collect blocks — deterministic overlap on any box.
    real = E._HOST_VERIFY["ed25519"]

    def slow_verify(pks, msgs, sigs):
        _t.sleep(0.02)
        return real(pks, msgs, sigs)

    monkeypatch.setitem(E._HOST_VERIFY, "ed25519", slow_verify)

    was = T.enabled()
    T.set_enabled(True)
    T.clear()
    try:
        n_callers, iters = 4, 3
        jobs = {c: make_jobs(8) for c in range(n_callers)}
        errs = []
        eng = E.get_engine()

        def caller(c):
            # Submit WITHOUT waiting (the blocksync verify-ahead shape):
            # later submissions arrive while earlier batches are in
            # flight, so the dispatch worker forms a new group per
            # in-flight window and the double buffer actually pipelines.
            try:
                handles = []
                for _ in range(iters):
                    handles.append(eng.submit("ed25519", *jobs[c]))
                    _t.sleep(0.005)  # land in distinct coalesce windows
                for h in handles:
                    assert all(h.result(timeout=120))
            except Exception as e:  # noqa: BLE001 - surface after join
                errs.append(e)

        threads = [threading.Thread(target=caller, args=(c,)) for c in range(n_callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        doc = T.export()
    finally:
        T.set_enabled(was)
        T.clear()

    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in spans}
    assert {"engine.submit", "engine.coalesce", "engine.dispatch",
            "engine.host_verify", "engine.collect"} <= names, names

    # flow correlation: some flow id must link a caller's submit span to
    # the collect span of the coalesced launch that carried it
    def flows(name):
        return {
            (e.get("args") or {}).get("flow")
            for e in spans
            if e["name"] == name and (e.get("args") or {}).get("flow")
        }

    linked = flows("engine.submit") & flows("engine.collect")
    assert linked, "no flow id links a submit span to a collect span"
    # submit and collect happen on different threads (caller vs worker)
    fid = next(iter(linked))
    sub_tid = next(e["tid"] for e in spans
                   if e["name"] == "engine.submit" and (e.get("args") or {}).get("flow") == fid)
    col_tid = next(e["tid"] for e in spans
                   if e["name"] == "engine.collect" and (e.get("args") or {}).get("flow") == fid)
    assert sub_tid != col_tid

    # telemetry: the workload moved the engine series
    assert _counter_value(m.launches) > launches_before
    assert _counter_value(m.overlap_seconds) > overlap_before, (
        "pipelined workload recorded no dispatch/collect overlap"
    )
    text = global_registry().gather()
    for series in (
        "tendermint_engine_queue_depth",
        "tendermint_engine_coalesce_factor_rows_bucket",
        "tendermint_engine_coalesced_group_size_count",
        "tendermint_engine_launch_latency_seconds_bucket",
        "tendermint_engine_collect_latency_seconds_bucket",
        "tendermint_engine_queue_wait_seconds_count",
        "tendermint_engine_overlap_seconds_total",
        "tendermint_engine_overlap_ratio",
        'tendermint_engine_path_rows_total{plane="ed25519",path="host",status="accept"}',
        'tendermint_engine_launches_total{plane="ed25519",path="host"}',
        "tendermint_engine_host_pool_busy_seconds_total",
    ):
        assert series in text, f"{series} missing from engine telemetry"
