"""Unified async verification engine (ops/engine.py).

Pins the tentpole contracts: coalescing with per-caller demux
(mixed-validity batches stay isolated per caller), worker exception
propagation (a dispatch-stage failure reaches the submitting caller and
the engine keeps serving), the oracle's verdicts on every route and
key type, a host-only node that never imports jax, autotune leaving
the CPU defaults untouched, and the msm tail-row alignment assertion
(ADVICE r5 medium).
Includes the tier-1 bench smoke that pushes one tiny coalesced batch
through the engine under JAX_PLATFORMS=cpu so the path cannot rot
between TPU windows.
"""

import os
import subprocess
import sys
import textwrap
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
    group, held = eng._take_group()
    assert group == [jobs[0], jobs[2]] and held == 0
    assert eng._pending == [jobs[1]]


def test_take_group_respects_row_cap(monkeypatch):
    monkeypatch.setattr(E, "MAX_COALESCE_ROWS", 4)
    eng = E.VerifyEngine()
    jobs = [E._Job("ed25519", [b"x"] * 3, [b"m"] * 3, [b"s"] * 3) for _ in range(3)]
    eng._pending = list(jobs)
    group, held = eng._take_group()
    assert group == [jobs[0]] and held == 0  # 3 + 3 > 4: second job waits
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


def _ed25519_corpus():
    corpus = [make_jobs(6), make_jobs(8, tamper_idx={0, 7}), make_jobs(5, tamper_idx={2})]
    oracle = lambda p, m, s: ref.verify(p, m, s, zip215=True)
    return corpus, Ed25519BatchVerifier, Ed25519PubKey, oracle


def _sr25519_corpus():
    from tendermint_tpu.crypto import sr25519 as sr

    corpus = []
    for n, tamper in ((6, ()), (8, (0, 7)), (5, (2,))):
        privs = [sr.Sr25519PrivKey.generate(b"route-%d-%d" % (n, i)) for i in range(n)]
        msgs = [b"sr-vote-%d" % i for i in range(n)]
        sigs = [p.sign(m) for p, m in zip(privs, msgs)]
        for i in tamper:
            sigs[i] = sigs[i][:10] + bytes([sigs[i][10] ^ 0xFF]) + sigs[i][11:]
        corpus.append(([p.pub_key().bytes() for p in privs], msgs, sigs))
    return corpus, sr.Sr25519BatchVerifier, sr.Sr25519PubKey, sr.verify


# route -> (DEVICE_BATCH_CUTOVER, MSM_BATCH_CUTOVER) that force it
_ROUTE_CUTOVERS = {"host": (1 << 30, 1 << 30), "bitmap": (4, 1 << 30), "two_phase_msm": (4, 4)}


@pytest.mark.parametrize("key_type", ["ed25519", "sr25519"])
@pytest.mark.parametrize("route", list(_ROUTE_CUTOVERS))
def test_engine_matches_the_oracle_on_every_route(monkeypatch, route, key_type):
    """Both batch verifiers submit to the engine and nothing else, so on
    each route the engine can choose (forced by the two cutovers) the
    (ok, bools) of a mixed-validity corpus are the pure-Python oracle's,
    and the launches are counted under that route's name."""
    from tendermint_tpu.metrics import engine_metrics

    corpus, verifier, pubkey, oracle = {"ed25519": _ed25519_corpus,
                                        "sr25519": _sr25519_corpus}[key_type]()
    device, msm = _ROUTE_CUTOVERS[route]
    monkeypatch.setenv("TM_TPU_CRYPTO", "on")
    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", device)
    monkeypatch.setattr(ed, "MSM_BATCH_CUTOVER", msm)

    def launches():
        return {labels["path"]: v for _, labels, v in engine_metrics().launches.samples()
                if labels["plane"] == key_type}

    before = launches()
    for pks, msgs, sigs in corpus:
        bv = verifier()
        for p, m, s in zip(pks, msgs, sigs):
            bv.add(pubkey(p), m, s)
        ok, bools = bv.verify()
        want = [oracle(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
        assert bools == want
        assert ok == all(want)
    def grown():
        return {p: v - before.get(p, 0.0) for p, v in launches().items() if v != before.get(p, 0.0)}

    # the collect worker counts a launch after it has woken the caller
    deadline = time.monotonic() + 10
    while grown() != {route: len(corpus)} and time.monotonic() < deadline:
        time.sleep(0.01)
    assert grown() == {route: len(corpus)}


_HOST_ONLY_NODE = """
    import sys

    from tendermint_tpu.crypto import ed25519_ref as ref
    from tendermint_tpu.crypto import sr25519 as sr
    from tendermint_tpu.crypto.ed25519 import Ed25519BatchVerifier, Ed25519PubKey
    from tendermint_tpu.mempool.preverify import EngineTxPreVerifier, make_sig_tx

    def tampered(sig):
        return sig[:10] + bytes([sig[10] ^ 0xFF]) + sig[11:]

    def verdicts(verifier, jobs):
        bv = verifier()
        for job in jobs:
            bv.add(*job)
        return bv.verify()

    sks = [ref.gen_privkey(bytes([i + 1]) * 32) for i in range(3)]
    ed_jobs = [(Ed25519PubKey(sk[32:]), b"vote", ref.sign(sk, b"vote")) for sk in sks]
    assert verdicts(Ed25519BatchVerifier, ed_jobs) == (True, [True] * 3)
    ed_jobs[1] = (ed_jobs[1][0], b"vote", tampered(ed_jobs[1][2]))
    assert verdicts(Ed25519BatchVerifier, ed_jobs) == (False, [True, False, True])

    privs = [sr.Sr25519PrivKey.generate(b"host-only-%d" % i) for i in range(3)]
    sr_jobs = [(p.pub_key(), b"vote", p.sign(b"vote")) for p in privs]
    assert verdicts(sr.Sr25519BatchVerifier, sr_jobs) == (True, [True] * 3)
    sr_jobs[2] = (sr_jobs[2][0], b"vote", tampered(sr_jobs[2][2]))
    assert verdicts(sr.Sr25519BatchVerifier, sr_jobs) == (False, [True, True, False])

    good = make_sig_tx(b"\\x11" * 32, b"pay=1")
    bad = good[:-1] + bytes([good[-1] ^ 1])
    assert EngineTxPreVerifier()([good, b"plain=1"]) == [True, None]
    assert EngineTxPreVerifier()([good, bad, b"plain=1"]) == [True, False, None]

    from tendermint_tpu.metrics import engine_metrics
    paths = {labels["path"] for _, labels, _ in engine_metrics().launches.samples()}
    assert paths == {"host"}, paths
    assert "jax" not in sys.modules, "a host-only node imported jax"
    print("HOST-ONLY-OK")
"""


def test_host_only_node_verifies_through_the_engine_without_importing_jax():
    """A node pinned to the host (TM_TPU_CRYPTO=off, the e2e core gate's
    pin) verifies commits of both key types and signed transactions
    through the engine's host plane, refuses a tampered signature in
    each, and never imports jax. A process of its own: this one has."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if not k.startswith("TM_TPU_")}
    env.update(TM_TPU_CRYPTO="off", TM_TPU_AUTOTUNE="off", PYTHONPATH=root)
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_HOST_ONLY_NODE)],
        env=env, cwd=root, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "HOST-ONLY-OK" in proc.stdout


def test_engine_host_plane_without_the_native_library(monkeypatch):
    """TM_TPU_NATIVE=off: the C loop is absent, _host_verify_ed25519
    falls back to one _single_verify a row, and the engine's verdicts
    are still the oracle's, ZIP-215 edge included."""
    from tendermint_tpu import native

    monkeypatch.setenv("TM_TPU_NATIVE", "off")
    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", 1 << 30)
    pks, msgs, sigs = make_jobs(5, tamper_idx={3})
    pks.append(ref.small_order_points()[1])
    msgs.append(b"anything")
    sigs.append(ref.compress(ref.IDENTITY) + b"\x00" * 32)
    assert native.host_verify_batch(pks, msgs, sigs) is None
    want = [ref.verify(p, m, s, zip215=True) for p, m, s in zip(pks, msgs, sigs)]
    assert want == [True, True, True, False, True, True]
    assert submit_and_wait(pks, msgs, sigs) == want


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


# ------------------------------------------------- jobs entered together


def _group_spy(monkeypatch):
    """The job counts and rows of every group _dispatch_group is given."""
    groups = []
    real = E.VerifyEngine._dispatch_group

    def spy(self, group, seq=0):
        groups.append([j.n for j in group])
        return real(self, group, seq)

    monkeypatch.setattr(E.VerifyEngine, "_dispatch_group", spy)
    return groups


@pytest.mark.parametrize("bad", [None, "second", "first", "both"])
def test_jobs_entered_together_are_one_group_and_each_reads_its_own_verdicts(monkeypatch, bad):
    """An engine of its own, its dispatch thread parked on an empty
    queue: two batches in one submit_together are one _dispatch_group
    call, every time (they enter under one hold of the lock), and the
    combined bitmap is cut back to each in order, a bad row in one
    leaving the other's verdicts all true."""
    from tendermint_tpu.metrics import engine_metrics

    first = make_jobs(5, tamper_idx={1} if bad in ("first", "both") else ())
    second = make_jobs(9, tamper_idx={0, 7} if bad in ("second", "both") else ())
    groups = _group_spy(monkeypatch)
    m = engine_metrics()
    together = _counter_value(m.jobs_submitted_together)
    submitted = _counter_value(m.submitted_jobs)
    eng = E.VerifyEngine()
    assert eng._pending == [] and not eng._started
    handles = eng.submit_together([("ed25519", *first, None), ("ed25519", *second, None)])
    got = [h.result(timeout=120) for h in handles]
    assert groups == [[5, 9]]
    assert got[0] == [i != 1 or bad not in ("first", "both") for i in range(5)]
    assert got[1] == [i not in (0, 7) or bad not in ("second", "both") for i in range(9)]
    assert _counter_value(m.jobs_submitted_together) == together + 2
    assert _counter_value(m.submitted_jobs) == submitted + 2
    # one job alone goes the way it went, and is not counted as entering beside another
    assert eng.submit("ed25519", *first).result(timeout=120) == got[0]
    assert groups == [[5, 9], [5]]
    assert _counter_value(m.jobs_submitted_together) == together + 2


def test_a_pair_past_the_row_cap_completes_as_two_groups(monkeypatch):
    monkeypatch.setattr(E, "MAX_COALESCE_ROWS", 8)
    groups = _group_spy(monkeypatch)
    first, second = make_jobs(5), make_jobs(6, tamper_idx={2})
    handles = E.VerifyEngine().submit_together(
        [("ed25519", *first, None), ("ed25519", *second, None)])
    assert [h.result(timeout=120) for h in handles] == [[True] * 5,
                                                        [i != 2 for i in range(6)]]
    assert groups == [[5], [6]]


def test_submit_together_queues_nothing_when_one_batch_is_refused():
    pks, msgs, sigs = make_jobs(3)
    eng = E.VerifyEngine()
    with pytest.raises(ValueError, match="ragged batch"):
        eng.submit_together([("ed25519", pks, msgs, sigs, None),
                             ("ed25519", pks[:2], msgs, sigs, None)])
    with pytest.raises(ValueError, match="unknown verification plane"):
        eng.submit_together([("ed25519", pks, msgs, sigs, None),
                             ("secp256k1", pks, msgs, sigs, None)])
    assert eng._pending == [] and not eng._started
    # an empty batch beside a full one: answered at once, the other queued alone
    empty, full = eng.submit_together([("ed25519", [], [], [], None),
                                       ("ed25519", pks, msgs, sigs, None)])
    assert empty.result(timeout=5) == [] and full.result(timeout=120) == [True] * 3


def test_the_trace_says_how_many_jobs_a_launch_carried():
    from tendermint_tpu import trace as T

    was = T.enabled()
    T.set_enabled(True)
    T.clear()
    try:
        handles = E.VerifyEngine().submit_together(
            [("ed25519", *make_jobs(4), "h7"), ("ed25519", *make_jobs(6), "h7")])
        assert all(all(h.result(timeout=120)) for h in handles)
        # the collect span closes after the callers are woken
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            spans = [e for e in T.export()["traceEvents"] if e.get("ph") == "X"]
            if any(e["name"] == "engine.collect" for e in spans):
                break
            time.sleep(0.01)
    finally:
        T.set_enabled(was)
        T.clear()
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e["args"])
    assert [(a["rows"], a["together"]) for a in by_name["engine.submit"]] == [(4, 2), (6, 2)]
    for name in ("engine.dispatch", "engine.collect"):
        (args,) = by_name[name]
        assert (args["jobs"], args["rows"], args["journeys"]) == (2, 10, ["h7"])
        assert args["reqs"] == [a["req"] for a in by_name["engine.submit"]]


def test_the_batch_verifiers_hand_their_batches_over_in_one_call(monkeypatch):
    """crypto/batch.py verify_async_together: two verifiers, one group,
    verify_async's contract for each; an empty one sends each its own way."""
    from tendermint_tpu.crypto import batch as crypto_batch

    def verifier(rows, tamper_idx=()):
        bv = Ed25519BatchVerifier()
        for pk, msg, sig in zip(*make_jobs(rows, tamper_idx)):
            bv.add(Ed25519PubKey(pk), msg, sig)
        return bv

    groups = _group_spy(monkeypatch)
    done = crypto_batch.verify_async_together([verifier(3), verifier(4, tamper_idx={3})])
    assert [c() for c in done] == [(True, [True] * 3), (False, [True, True, True, False])]
    assert groups == [[3, 4]]
    done = crypto_batch.verify_async_together([verifier(0), verifier(2)])
    assert [c() for c in done] == [(False, []), (True, [True, True])]
    assert groups == [[3, 4], [2]]


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


def test_rlc_precheck_refusal_dispatches_bitmap_immediately(monkeypatch):
    """ADVICE r5 (low): when the RLC dispatch refuses at precheck,
    _dispatch_group must launch the bitmap kernel itself (launch-now/
    collect-later preserved), not leave it to the collect thunk."""
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
    # s >= L: well-formed 64 bytes but fails the RLC precheck, so
    # _dispatch_rlc returns None
    s = int.from_bytes(sigs[2][32:], "little")
    sigs[2] = sigs[2][:32] + int.to_bytes(s + ref.L, 32, "little")
    thunk, path = E.VerifyEngine()._dispatch_group([E._Job("ed25519", pks, msgs, sigs)])
    assert path == "two_phase_msm"
    assert dispatched_at == ["dispatch"], "bitmap not dispatched before the collect thunk"
    assert thunk() == [True, True, False, True, True]
    assert dispatched_at == ["dispatch"], "the collect thunk dispatched a second bitmap"


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


# --------------------------------------- coalescing into a bucket no program has run at


@pytest.mark.parametrize("case,groups,held", [
    # apart, and no launch of the plane has run at 2048 rows: two groups at 1024
    ("apart", [[667], [1000]], 1),
    # apart, after a launch at 2048 rows: one group there
    ("apart_after_2048", [[667, 1000]], 0),
    # in one submit_together call: one group whatever has run
    ("together", [[667, 1000]], 0),
], ids=["apart", "apart_after_2048", "together"])
def test_jobs_queued_apart_join_only_at_a_bucket_already_run(monkeypatch, case, groups, held):
    """The verify-ahead's 667-row proof and the full check's 1000 rows,
    queued while the dispatch thread was held: joined, they would launch
    at 2048 rows and load that program inside the caller's wait
    (ops/launched.py, VerifyEngine._take_group)."""
    from tendermint_tpu import trace as T
    from tendermint_tpu.metrics import engine_metrics
    from tendermint_tpu.ops import launched

    monkeypatch.setattr(launched, "_ROWS", {})
    monkeypatch.setenv("TM_TPU_CRYPTO", "on")
    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", 64)
    monkeypatch.setattr(ed, "MSM_BATCH_CUTOVER", 1 << 30)
    seen = []

    def dispatch(self, group, seq=0):  # the route's bookkeeping, with no kernel
        rows = sum(j.n for j in group)
        seen.append(([j.n for j in group], launched.pad_pow2(rows)))
        launched.note(group[0].plane, launched.pad_pow2(rows))
        return (lambda: [True] * rows), "bitmap"

    monkeypatch.setattr(E.VerifyEngine, "_dispatch_group", dispatch)
    if case == "apart_after_2048":
        launched.note("ed25519", 2048)
    m = engine_metrics()
    before = _counter_value(m.coalesce_held_jobs)
    batches = [("ed25519", [b"k"] * n, [b"m"] * n, [b"s"] * n, None) for n in (667, 1000)]
    eng = E.VerifyEngine()
    eng._started = True  # the dispatch thread held: both jobs queue before it looks
    if case == "together":
        handles = eng.submit_together(batches)
    else:
        handles = [eng.submit_together([b])[0] for b in batches]
    assert [j.n for j in eng._pending] == [667, 1000]
    was = T.enabled()
    T.set_enabled(True)
    T.clear()
    try:
        eng._started = False
        eng._ensure_started()
        assert [h.result(timeout=60) for h in handles] == [[True] * 667, [True] * 1000]
        coalesce = [e["args"] for e in T.export()["traceEvents"]
                    if e.get("ph") == "X" and e["name"] == "engine.coalesce"]
    finally:
        T.set_enabled(was)
        T.clear()
    assert [g for g, _ in seen] == groups
    assert [b for _, b in seen] == ([1024, 1024] if held else [2048])
    assert coalesce[0]["held"] == held
    assert _counter_value(m.coalesce_held_jobs) == before + held
