"""Batched TPU-kernel verification vs the oracle, incl. ZIP-215 edges and
the sharded multi-device path."""

import secrets

from tendermint_tpu.crypto import ed25519_ref as ref
from tendermint_tpu.crypto.batch import create_batch_verifier, supports_batch_verifier
from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey, Ed25519PubKey
from tendermint_tpu.ops import verify as V


def make_jobs(n, tamper_idx=()):
    pks, msgs, sigs = [], [], []
    for i in range(n):
        priv = ref.gen_privkey(secrets.token_bytes(32))
        msg = b"block-vote-%d" % i + secrets.token_bytes(16)
        sig = ref.sign(priv, msg)
        if i in tamper_idx:
            sig = sig[:10] + bytes([sig[10] ^ 0xFF]) + sig[11:]
        pks.append(priv[32:])
        msgs.append(msg)
        sigs.append(sig)
    return pks, msgs, sigs


def test_verify_batch_all_valid():
    pks, msgs, sigs = make_jobs(5)
    got = V.verify_batch(pks, msgs, sigs)
    assert got.all()


def test_verify_batch_bad_indices():
    pks, msgs, sigs = make_jobs(7, tamper_idx={2, 5})
    got = V.verify_batch(pks, msgs, sigs)
    for i in range(7):
        assert bool(got[i]) == (i not in {2, 5}), i


def test_verify_batch_matches_oracle_on_edges():
    # s >= L rejected; small-order pubkeys accepted per ZIP-215; garbage
    # encodings rejected — all must match the oracle exactly.
    pks, msgs, sigs = make_jobs(2)
    # s + L malleability
    s = int.from_bytes(sigs[0][32:], "little")
    sigs.append(sigs[0][:32] + int.to_bytes(s + ref.L, 32, "little"))
    pks.append(pks[0])
    msgs.append(msgs[0])
    # small-order pubkey, identity R, s = 0 (valid under cofactored eq)
    so = ref.small_order_points()[1]
    pks.append(so)
    msgs.append(b"anything")
    sigs.append(ref.compress(ref.IDENTITY) + b"\x00" * 32)
    # non-point pubkey
    y = 2
    while ref.decompress(int.to_bytes(y, 32, "little")) is not None:
        y += 1
    pks.append(int.to_bytes(y, 32, "little"))
    msgs.append(b"x")
    sigs.append(sigs[0])
    got = V.verify_batch(pks, msgs, sigs)
    want = [ref.verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    assert [bool(b) for b in got] == want
    assert want == [True, True, False, True, False]


def test_cached_kernel_matches_uncached():
    # Same batch through verify_batch and verify_batch_cached, including
    # repeated keys, a tampered sig, and the ZIP-215 edge encodings.
    pks, msgs, sigs = make_jobs(6, tamper_idx=(2,))
    pks[4], msgs[4] = pks[0], msgs[4]  # repeated key, different msg
    sigs[4] = ref.sign(ref.gen_privkey(secrets.token_bytes(32)), msgs[4])  # wrong key
    so = ref.small_order_points()[1]
    pks.append(so)
    msgs.append(b"anything")
    sigs.append(ref.compress(ref.IDENTITY) + b"\x00" * 32)
    uncached = [bool(b) for b in V.verify_batch(pks, msgs, sigs)]
    cached1 = [bool(b) for b in V.verify_batch_cached(pks, msgs, sigs)]
    cached2 = [bool(b) for b in V.verify_batch_cached(pks, msgs, sigs)]  # all hits
    assert uncached == cached1 == cached2
    assert not cached1[2] and not cached1[4] and cached1[6]


def test_pubkey_cache_eviction_and_overflow():
    cache = V.PubkeyCache(capacity=4)
    pks, msgs, sigs = make_jobs(3)
    slots1 = cache.ensure(pks)
    assert len(set(slots1.tolist())) == 3
    # refresh pk0, insert two more -> pk1 (now coldest) evicted
    cache.ensure([pks[0]])
    pks2, _, _ = make_jobs(2)
    cache.ensure(pks2)
    assert pks[1] not in cache._lru and pks[0] in cache._lru
    # eviction must never pop a key used by the same batch
    extra_pks, _, _ = make_jobs(2)
    slots = cache.ensure([pks[0]] + pks2 + extra_pks[:1])
    assert slots is not None and len(slots) == 4
    # more distinct keys than capacity -> fallback signal
    many, _, _ = make_jobs(5)
    assert cache.ensure(many) is None
    # and the public path still verifies correctly via fallback
    mpks, mmsgs, msigs = make_jobs(5, tamper_idx=(3,))
    import tendermint_tpu.ops.verify as Vm
    old = Vm._PK_CACHE
    Vm._PK_CACHE = V.PubkeyCache(capacity=4)
    try:
        got = [bool(b) for b in V.verify_batch_cached(mpks, mmsgs, msigs)]
    finally:
        Vm._PK_CACHE = old
    assert got == [True, True, True, False, True]


def test_batch_verifier_interface():
    pks, msgs, sigs = make_jobs(4, tamper_idx={1})
    bv = create_batch_verifier(Ed25519PubKey(pks[0]))
    for p, m, s in zip(pks, msgs, sigs):
        bv.add(Ed25519PubKey(p), m, s)
    all_ok, bitmap = bv.verify()
    assert not all_ok
    assert bitmap == [True, False, True, True]
    assert supports_batch_verifier(Ed25519PubKey(pks[0]))


def test_single_verify_pubkey():
    priv = Ed25519PrivKey.generate()
    msg = b"hello"
    sig = priv.sign(msg)
    assert priv.pub_key().verify_signature(msg, sig)
    assert not priv.pub_key().verify_signature(msg + b"!", sig)
    assert len(priv.pub_key().address()) == 20


def test_sharded_verify_8_devices(small_mesh_cache):
    import jax

    from tendermint_tpu.parallel import sharded_verify as S

    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    mesh = S.make_mesh()
    pks, msgs, sigs = make_jobs(19, tamper_idx={3})
    bitmap, all_valid = S.verify_batch_sharded(mesh, pks, msgs, sigs)
    assert not all_valid
    assert [bool(b) for b in bitmap] == [i != 3 for i in range(19)]
    bitmap2, all_valid2 = S.verify_batch_sharded(mesh, *make_jobs(8))
    assert all_valid2 and bitmap2.all()


def test_sharded_verify_sr25519_8_devices(small_mesh_cache):
    """The sr25519 plane shards over the mesh exactly like ed25519:
    per-shard kernels, psum AND-reduce, fault localization."""
    from tendermint_tpu.crypto import sr25519 as sr
    from tendermint_tpu.parallel import sharded_verify as SV

    mesh = SV.make_mesh(8)
    priv = sr.Sr25519PrivKey.generate(b"shard-sr")
    pk = priv.pub_key().bytes()
    n = 64
    msgs = [b"sharded-sr-%02d" % i for i in range(n)]
    sigs = [priv.sign(m) for m in msgs]
    bitmap, all_ok = SV.verify_batch_sharded(mesh, [pk] * n, msgs, sigs, key_type="sr25519")
    assert all_ok and bitmap.all()

    bad = bytearray(sigs[37]); bad[2] ^= 1; sigs[37] = bytes(bad)
    bitmap, all_ok = SV.verify_batch_sharded(mesh, [pk] * n, msgs, sigs, key_type="sr25519")
    assert not all_ok
    assert not bitmap[37] and bitmap.sum() == n - 1  # fault localized


def test_multihost_entry_single_controller(small_mesh_cache):
    """parallel.multihost: on a single controller the local entry is
    exactly the sharded path, and initialize() is a safe no-op."""
    import jax
    from tendermint_tpu.parallel import multihost as mh
    from tendermint_tpu.parallel import sharded_verify as sv

    mh.initialize()  # no coordinator: no-op
    mesh = mh.global_mesh()
    assert mesh.devices.size == len(jax.devices())
    pks, msgs, sigs = make_jobs(16, tamper_idx=(3,))
    bm, ok = mh.verify_batch_sharded_local(mesh, pks, msgs, sigs)
    bm2, ok2 = sv.verify_batch_sharded(mesh, pks, msgs, sigs)
    assert [bool(b) for b in bm] == [bool(b) for b in bm2]
    assert ok == ok2 == False  # noqa: E712


def test_split_cached_plane_agrees_with_the_uncached_kernel_and_the_oracle():
    """The split-ladder cached kernel accepts exactly what the uncached
    verify_kernel and the pure-Python oracle accept: the same batch
    (valid + tampered + small-order edge) through a bare PubkeyCache,
    whose default entries are the split form."""
    pks, msgs, sigs = make_jobs(4, tamper_idx=(1,))
    so = ref.small_order_points()[1]
    pks.append(so); msgs.append(b"edge"); sigs.append(ref.compress(ref.IDENTITY) + b"\x00" * 32)

    got_split = V.collect(V.dispatch_cached(
        V.PubkeyCache(capacity=8), V.prepare_batch, V.verify_kernel_cached_split,
        V.verify_batch_async, pks, msgs, sigs))
    got_uncached = V.verify_batch(pks, msgs, sigs)
    want = [ref.verify(p, m, s, zip215=True) for p, m, s in zip(pks, msgs, sigs)]
    assert [bool(b) for b in got_split] == [bool(b) for b in got_uncached] == want
    assert want == [True, False, True, True, True]


def test_pubkey_cache_fill_does_not_block_hits():
    """tmcheck hold_budget regression: PubkeyCache used to run the
    table-build device call UNDER the cache lock, so a concurrent
    verifier over already-cached keys stalled behind every miss fill
    (1.5s observed under CPU emulation). Fills now reserve under the
    lock, build unlocked, and publish under the lock — a hit-only
    batch proceeds while a fill is in flight, and a second batch
    needing the SAME keys waits for the published tables."""
    import threading
    import time as _time

    import jax.numpy as jnp

    gate = threading.Event()
    building = threading.Event()
    arm = threading.Event()

    def gated_build(enc):
        # deterministic stub tables; once armed, the fill parks on the
        # gate to simulate a slow device launch (enc is pow2-PADDED, so
        # row count can't distinguish the prefill from the real fill)
        n = int(enc.shape[0])
        if arm.is_set():
            building.set()
            assert gate.wait(timeout=10)
        tables = jnp.tile(
            jnp.arange(n, dtype=jnp.int16).reshape(n, 1, 1, 1, 1), (1, V.PK_SPLITS, 16, 4, 32)
        )
        return tables, jnp.ones((n,), bool)

    cache = V.PubkeyCache(capacity=8, build_fn=gated_build)
    hit_key = b"\x01" * 32
    cache.ensure([hit_key])  # prefill before arming the gate
    arm.set()
    miss_keys = [bytes([0x10 + i]) * 32 for i in range(3)]
    # the filler batch SHARES the hot cached key: it gets an eviction
    # pin, but its published table must stay readable during the build
    fill_batch = [hit_key] + miss_keys

    result = {}

    def filler():
        slots, tables, _ = cache.ensure_snapshot(fill_batch)
        result["slots"], result["tables"] = slots[1:], tables  # miss rows

    t = threading.Thread(target=filler, daemon=True)
    t.start()
    assert building.wait(timeout=10), "fill never reached the build"
    # the fill is mid-build: a hit-only batch must NOT block on it —
    # even though its key is part of (and pinned by) the fill batch
    t0 = _time.monotonic()
    slots, _tables, oks = cache.ensure_snapshot([hit_key])
    assert _time.monotonic() - t0 < 1.0, "hit batch stalled behind a miss fill"
    assert slots is not None and len(slots) == 1
    # a batch over the SAME pending keys must wait for publication
    waited = {}

    def waiter():
        waited["slots"], waited["tables"], _ = cache.ensure_snapshot(miss_keys)

    w = threading.Thread(target=waiter, daemon=True)
    w.start()
    _time.sleep(0.1)
    assert "slots" not in waited  # parked on the pending event
    gate.set()
    t.join(timeout=10)
    w.join(timeout=10)
    assert sorted(result["slots"].tolist()) == sorted(waited["slots"].tolist())
    # published tables really landed in the reserved slots
    import numpy as _np

    got = _np.asarray(result["tables"])[result["slots"]]
    assert {int(x) for x in got[:, 0, 0, 0, 0]} == {0, 1, 2}
