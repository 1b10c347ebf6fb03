"""TxMempool tests (ref: internal/mempool/mempool_test.go, cache_test.go)."""

import pytest

from tendermint_tpu.abci import types as abci
from tendermint_tpu.mempool import LRUTxCache, TxInCacheError, TxMempool, tx_key


class PriorityApp(abci.BaseApplication):
    """CheckTx returns priority = int prefix of the tx ('<prio>:payload'),
    rejects txs starting with 'bad', and on recheck rejects 'stale'."""

    def check_tx(self, req):
        tx = req.tx
        if tx.startswith(b"bad"):
            return abci.ResponseCheckTx(code=1, log="rejected")
        if req.type == 1 and tx.startswith(b"stale"):
            return abci.ResponseCheckTx(code=2, log="stale on recheck")
        prio = 0
        if b":" in tx:
            head = tx.split(b":", 1)[0]
            try:
                prio = int(head)
            except ValueError:
                prio = 0
        return abci.ResponseCheckTx(code=0, priority=prio, gas_wanted=1)


class _DirectClient:
    def __init__(self, app):
        self._app = app

    def check_tx(self, req):
        return self._app.check_tx(req)


def make_pool(**kw):
    return TxMempool(_DirectClient(PriorityApp()), **kw)


def test_check_tx_admits_and_dedups():
    mp = make_pool()
    assert mp.check_tx(b"5:aaa").is_ok
    assert mp.size() == 1
    with pytest.raises(TxInCacheError):
        mp.check_tx(b"5:aaa")
    assert mp.size() == 1


def test_rejected_tx_not_added_and_not_cached():
    mp = make_pool()
    res = mp.check_tx(b"bad-tx")
    assert not res.is_ok
    assert mp.size() == 0
    # not kept in cache -> can be submitted again
    res2 = mp.check_tx(b"bad-tx")
    assert not res2.is_ok


def test_reap_priority_order_with_fifo_tiebreak():
    mp = make_pool()
    mp.check_tx(b"1:low")
    mp.check_tx(b"9:high")
    mp.check_tx(b"5:mid-a")
    mp.check_tx(b"5:mid-b")
    txs = mp.reap_max_bytes_max_gas(-1, -1)
    assert txs == [b"9:high", b"5:mid-a", b"5:mid-b", b"1:low"]


def test_reap_respects_byte_and_gas_budgets():
    mp = make_pool()
    mp.check_tx(b"9:aaaaaaaa")  # 10 bytes
    mp.check_tx(b"5:bbbbbbbb")
    mp.check_tx(b"1:cccccccc")
    assert len(mp.reap_max_bytes_max_gas(21, -1)) == 2  # 2×10 fits, 3rd doesn't
    assert len(mp.reap_max_bytes_max_gas(-1, 2)) == 2  # gas_wanted=1 each
    assert mp.reap_max_txs(1) == [b"9:aaaaaaaa"]


def test_update_removes_committed_and_rechecks():
    mp = make_pool()
    mp.check_tx(b"7:keep")
    mp.check_tx(b"stale:gone-on-recheck")
    mp.check_tx(b"3:committed")
    assert mp.size() == 3
    mp.lock()
    try:
        mp.update(
            1,
            [b"3:committed"],
            [abci.ExecTxResult(code=0)],
            recheck=True,
        )
    finally:
        mp.unlock()
    # committed tx removed; stale tx evicted by recheck; keep survives
    assert mp.size() == 1
    assert mp.reap_max_txs(-1) == [b"7:keep"]
    # committed tx key remains cached: replays rejected
    with pytest.raises(TxInCacheError):
        mp.check_tx(b"3:committed")


def test_full_mempool_errors():
    mp = make_pool(size=2)
    mp.check_tx(b"1:a")
    mp.check_tx(b"1:b")
    with pytest.raises(RuntimeError):
        mp.check_tx(b"1:c")


def test_txs_available_signal():
    mp = make_pool()
    mp.enable_txs_available()
    assert not mp.wait_txs_available(timeout=0.01)
    mp.check_tx(b"5:x")
    assert mp.wait_txs_available(timeout=1.0)


def test_remove_tx_by_key():
    mp = make_pool()
    mp.check_tx(b"5:x")
    mp.remove_tx_by_key(tx_key(b"5:x"))
    assert mp.size() == 0
    # removed from cache too -> re-submittable
    assert mp.check_tx(b"5:x").is_ok


def test_lru_cache_eviction():
    c = LRUTxCache(2)
    assert c.push(b"a") and c.push(b"b")
    assert not c.push(b"a")  # refreshes 'a'
    assert c.push(b"c")  # evicts 'b' (least recent)
    assert c.has(b"a") and c.has(b"c") and not c.has(b"b")


def test_ttl_num_blocks_purges_old_txs():
    """ref: purgeExpiredTxs (mempool.go:735) — txs older than
    ttl-num-blocks heights are evicted at Update and leave the cache so
    they can be resubmitted."""
    pool = make_pool(ttl_num_blocks=2)
    pool.check_tx(b"1:old")
    # advance 3 heights with unrelated commits
    for h in (1, 2, 3):
        pool.update(h, [], [], recheck=False)
    assert pool.size() == 0
    # purged from cache too: resubmission is accepted, not TxInCacheError
    pool.check_tx(b"1:old")
    assert pool.size() == 1


def test_ttl_duration_purges_old_txs(monkeypatch):
    import tendermint_tpu.mempool.mempool as mp

    pool = make_pool(ttl_duration=10.0)
    pool.check_tx(b"1:aged")
    now = mp.time.monotonic()
    monkeypatch.setattr(mp.time, "monotonic", lambda: now + 11.0)
    pool.update(1, [], [], recheck=False)
    assert pool.size() == 0


def test_ttl_zero_keeps_txs():
    pool = make_pool()
    pool.check_tx(b"1:keep")
    for h in range(1, 6):
        pool.update(h, [], [], recheck=False)
    assert pool.size() == 1


def test_max_gas_admission_rejected():
    """PostCheckMaxGas analog (ref: types.go:131): a tx wanting more
    gas than a block may carry is rejected at admission (it could never
    be reaped) and evicted from the cache so a later resubmission under
    a raised cap is re-evaluated."""
    import pytest

    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.abci import LocalClient
    from tendermint_tpu.mempool.mempool import TxMempool

    class GasApp(abci.BaseApplication):
        def check_tx(self, req):
            return abci.ResponseCheckTx(code=abci.CODE_TYPE_OK, gas_wanted=500)

    from tendermint_tpu.mempool.mempool import TxPolicyError

    mp = TxMempool(LocalClient(GasApp()), max_gas=100)
    # a POLICY error (sender not at fault — reactors must not evict)
    with pytest.raises(TxPolicyError, match="block max gas"):
        mp.check_tx(b"expensive-tx")
    assert mp.size() == 0
    # raise the cap (on-chain param change): the SAME tx is admitted
    mp.max_gas = 1000
    res = mp.check_tx(b"expensive-tx")
    assert res.is_ok and mp.size() == 1
    # LOWER the cap (params changed again): recheck must flush the
    # now-over-cap tx, or its priority would block every reap forever
    mp.max_gas = 100
    mp.lock()
    try:
        mp.update(2, [], [], recheck=True)
    finally:
        mp.unlock()
    assert mp.size() == 0, "over-cap tx survived recheck under the lowered cap"
    # unlimited (-1) never rejects
    mp2 = TxMempool(LocalClient(GasApp()), max_gas=-1)
    assert mp2.check_tx(b"any").is_ok


# ------------------------------------------------- batched admission


class GasCapApp(abci.BaseApplication):
    """CheckTx returns gas_wanted = int prefix ('<gas>:payload')."""

    def check_tx(self, req):
        gas = 1
        if b":" in req.tx:
            try:
                gas = int(req.tx.split(b":", 1)[0])
            except ValueError:
                gas = 1
        return abci.ResponseCheckTx(code=0, gas_wanted=gas)


def _outcome_sig(o):
    """Comparable signature of a check_tx outcome (response or raise)."""
    if isinstance(o, Exception):
        return type(o).__name__
    return ("res", o.code, o.priority, o.gas_wanted)


def _pool_state(mp):
    with mp._mtx:
        return {
            "txs": [(w.tx, w.priority, sorted(w.peers)) for w in mp._txs.values()],
            "total_bytes": mp._total_bytes,
            "cached": sorted(mp._cache._map.keys()),
        }


def _run_sequential(mp, txs, senders):
    out = []
    for tx, sender in zip(txs, senders):
        try:
            out.append(mp.check_tx(tx, sender=sender))
        except Exception as e:  # noqa: BLE001 - collecting raise outcomes
            out.append(e)
    return out


EQUIVALENCE_FLOODS = [
    # plain admits + app rejects + duplicate inside batch
    (
        dict(),
        [b"5:a", b"bad-x", b"5:a", b"1:b", b"bad-x", b"9:c"],
        ["", "", "p1", "", "", "p2"],
    ),
    # full-pool mid-batch: size 3, five valid txs -> last two full
    (dict(size=3), [b"1:a", b"1:b", b"1:c", b"1:d", b"1:e"], [""] * 5),
    # oversize + full + dup interleaved
    (
        dict(size=2, max_tx_bytes=8),
        [b"1:a", b"longer-than-8-bytes", b"1:a", b"1:b", b"1:c"],
        ["s1", "", "s2", "", ""],
    ),
    # gas-cap rejects (max_gas=100): over-cap evicted from cache
    (
        dict(max_gas=100, app=GasCapApp),
        [b"50:ok", b"500:over", b"500:over", b"100:edge"],
        [""] * 4,
    ),
    # keep_invalid_txs_in_cache: rejected txs stay cached
    (
        dict(keep_invalid_txs_in_cache=True),
        [b"bad-x", b"bad-x", b"5:a"],
        [""] * 3,
    ),
]


@pytest.mark.parametrize("case", range(len(EQUIVALENCE_FLOODS)))
def test_check_tx_batch_equivalent_to_sequential(case):
    """ISSUE 6 acceptance: batched admission is byte-identical in
    accept/reject outcomes, cache contents, peer routing, and final
    pool state to N sequential check_tx calls — including
    duplicate-inside-batch, full-pool mid-batch, oversize, and
    gas-cap rejects."""
    kw, txs, senders = EQUIVALENCE_FLOODS[case]
    kw = dict(kw)
    app_cls = kw.pop("app", PriorityApp)
    seq = TxMempool(_DirectClient(app_cls()), **kw)
    bat = TxMempool(_DirectClient(app_cls()), **kw)
    seq_out = _run_sequential(seq, txs, senders)
    bat_out = bat.check_tx_batch(txs, senders)
    assert [_outcome_sig(o) for o in seq_out] == [_outcome_sig(o) for o in bat_out]
    assert _pool_state(seq) == _pool_state(bat)
    assert seq.reap_max_txs(-1) == bat.reap_max_txs(-1)


def test_check_tx_batch_senders_and_available_signal():
    mp = make_pool()
    mp.enable_txs_available()
    out = mp.check_tx_batch([b"5:x", b"3:y"], ["peerA", "peerB"])
    assert all(o.is_ok for o in out)
    assert mp.wait_txs_available(timeout=1.0)
    # duplicate from another peer records the alternate route
    out2 = mp.check_tx_batch([b"5:x"], ["peerC"])
    from tendermint_tpu.mempool.mempool import TxInCacheError as TICE

    assert isinstance(out2[0], TICE)
    wtx = next(iter(mp._txs.values()))
    assert wtx.peers == {"peerA", "peerC"}


def test_check_tx_batch_uses_native_key_hashing():
    from tendermint_tpu.mempool.mempool import tx_keys_batch

    txs = [b"k%d" % i for i in range(100)]
    assert tx_keys_batch(txs) == [tx_key(t) for t in txs]


def test_recheck_releases_lock_while_responses_in_flight():
    """Regression: _recheck_txs must not hold the mempool lock across
    the ABCI round — admissions (and reaps) proceed while a recheck is
    blocked on the app."""
    import threading
    import time as _t

    gate = threading.Event()
    entered = threading.Event()

    class SlowRecheckApp(abci.BaseApplication):
        def check_tx(self, req):
            if req.type == 1:  # recheck: block until released
                entered.set()
                assert gate.wait(10), "recheck gate never released"
            return abci.ResponseCheckTx(code=0, gas_wanted=1)

    mp = TxMempool(_DirectClient(SlowRecheckApp()))
    mp.check_tx(b"1:seed")

    def updater():
        mp.lock()
        try:
            mp.update(1, [], [], recheck=True)
        finally:
            mp.unlock()

    t = threading.Thread(target=updater, daemon=True)
    t.start()
    assert entered.wait(5), "recheck never reached the app"
    # the recheck is parked inside the app with update()'s caller
    # holding the lock — admission must still get through
    t0 = _t.monotonic()
    res = mp.check_tx(b"5:while-rechecking")
    admit_latency = _t.monotonic() - t0
    assert res.is_ok and admit_latency < 2.0, (
        f"admission blocked {admit_latency:.1f}s behind an in-flight recheck"
    )
    assert mp.reap_max_txs(-1)  # reap must not block either
    gate.set()
    t.join(timeout=10)
    assert not t.is_alive()
    # both txs survive: the mid-recheck admission was not clobbered
    assert mp.size() == 2


def test_reap_order_cache_invalidation():
    """The cached priority view must invalidate on insert, remove, and
    recheck priority changes — never serve a stale order."""
    mp = make_pool()
    mp.check_tx(b"1:a")
    mp.check_tx(b"9:b")
    assert mp.reap_max_txs(-1) == [b"9:b", b"1:a"]  # builds the cache
    mp.check_tx(b"5:c")  # insert invalidates
    assert mp.reap_max_txs(-1) == [b"9:b", b"5:c", b"1:a"]
    mp.remove_tx_by_key(tx_key(b"9:b"))  # remove invalidates
    assert mp.reap_max_txs(-1) == [b"5:c", b"1:a"]
    assert mp.reap_max_bytes_max_gas(-1, -1) == [b"5:c", b"1:a"]


def test_async_batch_admitter_drains_and_backpressures():
    from tendermint_tpu.mempool.mempool import AsyncBatchAdmitter

    mp = make_pool()
    adm = AsyncBatchAdmitter(mp, maxsize=8, max_batch=4)
    # overfill WITHOUT the worker running: backpressure is observable
    adm._started = True  # suppress the worker
    assert all(adm.submit(b"1:t%d" % i) for i in range(8))
    assert not adm.submit(b"1:overflow"), "full queue must refuse"
    # now let a real worker drain it
    adm._started = False
    adm._ensure_started()
    deadline = __import__("time").monotonic() + 5
    while mp.size() < 8 and __import__("time").monotonic() < deadline:
        __import__("time").sleep(0.02)
    assert mp.size() == 8, f"admitter drained {mp.size()}/8"


# ------------------------------------------------ engine pre-verification


def test_preverify_envelope_roundtrip_and_verdicts():
    from tendermint_tpu.mempool.preverify import (
        EngineTxPreVerifier,
        make_sig_tx,
        parse_sig_tx,
    )

    good = make_sig_tx(b"\x11" * 32, b"pay=1")
    pk, sig, payload = parse_sig_tx(good)
    assert payload == b"pay=1" and len(pk) == 32 and len(sig) == 64
    assert parse_sig_tx(b"plain=1") is None
    bad = good[:-1] + bytes([good[-1] ^ 1])
    verdicts = EngineTxPreVerifier()([good, bad, b"plain=1"])
    assert verdicts == [True, False, None]


def test_preverify_batch_admission_outcomes():
    """Signed-flood admission: invalid signatures are rejected before
    the app, valid and unsigned txs admit; one tx at a time gives the
    same verdicts."""
    from tendermint_tpu.mempool.preverify import EngineTxPreVerifier, make_sig_tx

    good = make_sig_tx(b"\x11" * 32, b"a=1")
    bad = good[:-1] + bytes([good[-1] ^ 1])
    plain = b"k=1"
    mp = TxMempool(_DirectClient(PriorityApp()), pre_verify=EngineTxPreVerifier())
    out = mp.check_tx_batch([good, bad, plain])
    assert out[0].is_ok and out[2].is_ok
    assert out[1].code == 1 and "signature" in out[1].log
    assert mp.size() == 2
    # rejected sig left the cache: resubmission re-evaluates
    out2 = mp.check_tx_batch([bad])
    assert out2[0].code == 1
    # sequential parity
    mp2 = TxMempool(_DirectClient(PriorityApp()), pre_verify=EngineTxPreVerifier())
    assert mp2.check_tx(good).is_ok
    assert mp2.check_tx(bad).code == 1


def test_batch_duplicates_reach_app_exactly_as_sequential():
    """Stateful-app safety: a duplicated-in-batch tx whose first
    occurrence is accepted must hit the app's CheckTx exactly once
    (the sequential count); a rejected-and-uncached first occurrence
    keeps the sequential twice-called behavior."""

    class CountingApp(abci.BaseApplication):
        def __init__(self):
            self.calls = []

        def check_tx(self, req):
            self.calls.append(req.tx)
            if req.tx.startswith(b"bad"):
                return abci.ResponseCheckTx(code=1)
            return abci.ResponseCheckTx(code=0, gas_wanted=1)

    for txs in ([b"ok-a", b"ok-a", b"ok-b"], [b"bad-a", b"bad-a", b"ok-b"]):
        seq_app, bat_app = CountingApp(), CountingApp()
        seq = TxMempool(_DirectClient(seq_app))
        bat = TxMempool(_DirectClient(bat_app))
        seq_out = _run_sequential(seq, txs, [""] * len(txs))
        bat_out = bat.check_tx_batch(txs)
        assert [_outcome_sig(o) for o in seq_out] == [_outcome_sig(o) for o in bat_out]
        # same MULTISET of app calls (stateful check-state advances the
        # same number of times per tx); exact interleaving may differ —
        # a rejected-first-occurrence duplicate replays through the
        # deferred pass after the pipelined round, just as a concurrent
        # sequential admitter could interleave
        assert sorted(bat_app.calls) == sorted(seq_app.calls), (
            f"app saw {bat_app.calls} batched vs {seq_app.calls} sequential"
        )
