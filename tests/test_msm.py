"""RLC/MSM batched verification (ops/msm.py) vs the oracle.

The MSM plane is the all-valid fast path (one randomized-linear-
combination equation for the whole batch, ref: crypto/ed25519/
ed25519.go:225-233); acceptance must satisfy:
  - every all-valid batch (including ZIP-215 oddballs) accepts
    DETERMINISTICALLY (a sum of per-signature identities is identity)
  - any invalid signature sinks the whole check (w.h.p. over z; pinned
    z in tests for determinism)
  - end-to-end acceptance through the two-phase dispatch stays
    byte-identical to the per-signature bitmap plane
"""

import secrets

import pytest

from tendermint_tpu.crypto import ed25519_ref as ref
from tendermint_tpu.ops import msm
from tendermint_tpu.ops import verify as V

from test_batch_verify import make_jobs

Z16 = bytes(range(1, 17))


def test_msm_all_valid_accepts():
    pks, msgs, sigs = make_jobs(8)
    assert msm.verify_batch_rlc(pks, msgs, sigs, z_raw=Z16 * 8) is True


def test_msm_tampered_sig_rejects():
    pks, msgs, sigs = make_jobs(8, tamper_idx={3})
    assert msm.verify_batch_rlc(pks, msgs, sigs, z_raw=Z16 * 8) is False


def test_msm_wrong_key_rejects():
    pks, msgs, sigs = make_jobs(8)
    pks[5] = ref.gen_privkey(secrets.token_bytes(32))[32:]
    assert msm.verify_batch_rlc(pks, msgs, sigs, z_raw=Z16 * 8) is False


def test_msm_padded_batch():
    # n = 9 pads to 16: padding rows must contribute nothing
    pks, msgs, sigs = make_jobs(9)
    assert msm.verify_batch_rlc(pks, msgs, sigs, z_raw=Z16 * 9) is True
    pks[8] = ref.gen_privkey(secrets.token_bytes(32))[32:]
    assert msm.verify_batch_rlc(pks, msgs, sigs, z_raw=Z16 * 9) is False


def test_msm_zip215_adversarial_all_valid():
    """The adversarial-but-VALID ZIP-215 vector set must accept
    deterministically: small-order pubkey with identity R and s = 0 is
    a valid cofactored signature the strict planes reject."""
    pks, msgs, sigs = make_jobs(6)
    so = ref.small_order_points()[1]
    pks.append(so)
    msgs.append(b"anything")
    sigs.append(ref.compress(ref.IDENTITY) + b"\x00" * 32)
    # another small-order point as R on a normal key: sig won't verify
    # unless it actually satisfies the equation — instead use a second
    # valid weird lane: the SAME small-order pubkey, small-order R, s=0
    so2 = ref.small_order_points()[2]
    pks.append(so)
    msgs.append(b"other")
    sigs.append(so2 + b"\x00" * 32)
    # oracle agreement first: every lane must be individually valid
    want = [ref.verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    bitmap = [bool(b) for b in V.verify_batch(pks, msgs, sigs)]
    assert bitmap == want
    got = msm.verify_batch_rlc(pks, msgs, sigs, z_raw=Z16 * 8)
    assert got is all(want)


def test_msm_s_malleability_falls_back():
    """s >= L fails the host precheck; the RLC path refuses (None ->
    False) so the caller localizes on the bitmap plane, which rejects
    that lane — end-to-end acceptance identical to the reference."""
    pks, msgs, sigs = make_jobs(3)
    s = int.from_bytes(sigs[0][32:], "little")
    sigs.append(sigs[0][:32] + int.to_bytes(s + ref.L, 32, "little"))
    pks.append(pks[0])
    msgs.append(msgs[0])
    assert msm.verify_batch_rlc_async(pks, msgs, sigs) is None
    assert msm.verify_batch_rlc(pks, msgs, sigs) is False


def test_msm_z_raw_validation():
    pks, msgs, sigs = make_jobs(3)
    with pytest.raises(ValueError, match="z_raw"):
        msm.verify_batch_rlc(pks, msgs, sigs, z_raw=Z16 * 2)


def test_msm_empty_batch():
    assert msm.verify_batch_rlc([], [], []) is False


def test_msm_sharded_8_devices():
    """Sharded RLC over the virtual 8-device mesh: per-shard equations
    with per-shard zs partials, one psum AND-reduce verdict."""
    from tendermint_tpu.parallel import sharded_verify as sv

    mesh = sv.make_mesh()
    assert mesh.devices.size == 8
    pks, msgs, sigs = make_jobs(64)
    assert sv.verify_batch_sharded_rlc(mesh, pks, msgs, sigs, z_raw=Z16 * 64) is True
    pks2, msgs2, sigs2 = make_jobs(64, tamper_idx={17})
    assert sv.verify_batch_sharded_rlc(mesh, pks2, msgs2, sigs2, z_raw=Z16 * 64) is False
    # uneven batch (n=50 -> padded per-shard)
    assert sv.verify_batch_sharded_rlc(mesh, pks[:50], msgs[:50], sigs[:50],
                                       z_raw=Z16 * 50) is True


def test_batch_verifier_two_phase_dispatch(monkeypatch):
    """Ed25519BatchVerifier routes through the MSM fast path when the
    batch is large enough, falling back to the bitmap plane on failure —
    final (ok, bitmap) must match the per-signature plane exactly."""
    import tendermint_tpu.crypto.ed25519 as ed

    monkeypatch.setenv("TM_TPU_CRYPTO", "on")
    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", 4)
    monkeypatch.setattr(ed, "MSM_BATCH_CUTOVER", 4)

    pks, msgs, sigs = make_jobs(8)
    bv = ed.Ed25519BatchVerifier()
    for p, m, s in zip(pks, msgs, sigs):
        bv.add(ed.Ed25519PubKey(p), m, s)
    ok, bools = bv.verify()
    assert ok is True and bools == [True] * 8

    bv2 = ed.Ed25519BatchVerifier()
    pks, msgs, sigs = make_jobs(8, tamper_idx={2, 6})
    for p, m, s in zip(pks, msgs, sigs):
        bv2.add(ed.Ed25519PubKey(p), m, s)
    ok2, bools2 = bv2.verify()
    assert ok2 is False
    assert bools2 == [i not in {2, 6} for i in range(8)]


def test_msm_sr25519_matches_bitmap_plane(monkeypatch):
    """sr25519 RLC (ristretto, prime order — identity by zero encoding)
    agrees with the per-signature sr25519 plane on both polarities, and
    the Sr25519BatchVerifier two-phase dispatch returns byte-identical
    results."""
    from tendermint_tpu.crypto import sr25519 as sr
    from tendermint_tpu.ops import msm as M
    from tendermint_tpu.ops import verify_sr as VS

    n = 8
    priv = sr.Sr25519PrivKey.generate(b"sr-msm-test")
    pk = priv.pub_key().bytes()
    msgs = [b"sr-msm-%d" % i for i in range(n)]
    sigs = [priv.sign(m) for m in msgs]
    z = Z16 * n
    assert M.collect_rlc(M.verify_batch_rlc_sr_async([pk] * n, msgs, sigs, z_raw=z)) is True
    bad = bytearray(sigs[5]); bad[1] ^= 1
    sigs2 = list(sigs); sigs2[5] = bytes(bad)
    assert M.collect_rlc(M.verify_batch_rlc_sr_async([pk] * n, msgs, sigs2, z_raw=z)) is False
    bitmap = VS.collect(VS.verify_batch_async([pk] * n, msgs, sigs2))
    assert [bool(b) for b in bitmap] == [i != 5 for i in range(n)]

    # two-phase dispatch via the public BatchVerifier
    import tendermint_tpu.crypto.ed25519 as ed

    monkeypatch.setenv("TM_TPU_CRYPTO", "on")
    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", 4)
    monkeypatch.setattr(ed, "MSM_BATCH_CUTOVER", 4)
    bv = sr.Sr25519BatchVerifier()
    for m, s in zip(msgs, sigs2):
        bv.add(sr.Sr25519PubKey(pk), m, s)
    ok, bools = bv.verify()
    assert ok is False and bools == [i != 5 for i in range(n)]
