"""Batched sr25519 (schnorrkel) verification on device.

Same split of labor as the ed25519 plane (ops/verify.py):
  host   — Merlin transcript challenges k = H(proto, pk, R) mod L,
           s < L range check, marker-bit check, input shaping
  device — ristretto decode of A, the joint [s]B - [k]A Straus ladder
           (shared with ed25519 — ops/curve.py:242), ristretto
           re-encoding, byte comparison against the wire R

The equation is R == encode([s]B - [k]A): schnorrkel compares compressed
encodings (no cofactor clearing — the ristretto group has prime order),
so a valid signature is exactly one whose R bytes re-emerge from the
ladder. ref: crypto/sr25519/batch.go:15-47 (the semantics this plane
implements); the batch RLC equation the voi backend uses is replaced by
the per-signature bitmap, which the callers need anyway
(types/validation.go:245-255 first-bad-index semantics).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import curve as C
from . import launched
from . import ristretto as R

L = 2**252 + 27742317777372353535851937790883648493


def verify_sr_kernel_impl(a_enc, r_enc, s_bytes, k_bytes):
    """(B, 32) uint8 arrays -> (B,) bool validity. a_enc/r_enc are
    ristretto encodings; s_bytes pre-checked < L with the marker bit
    cleared; k_bytes the Merlin challenge mod L."""
    a = a_enc.T.astype(jnp.int32)  # (32, B) limb-major
    r = r_enc.T.astype(jnp.int32)
    s = s_bytes.T.astype(jnp.int32)
    k = k_bytes.T.astype(jnp.int32)
    a_pt, a_ok = R.decode(a)
    q = C.double_scalar_mul_base(s, k, C.point_neg(a_pt))  # [s]B - [k]A
    enc = R.encode(q)
    return a_ok & jnp.all(enc == r, axis=0)


verify_sr_kernel = jax.jit(verify_sr_kernel_impl)


def build_sr_tables_split_impl(a_enc):
    """Split-plane cache fill (see ops/verify.py build_pk_tables_split):
    ristretto decode + negate + power tables, (B, S, 16, 4, 32) int16."""
    from .verify import PK_SPLITS

    a = a_enc.T.astype(jnp.int32)
    a_pt, ok = R.decode(a)
    tabs = C.build_power_tables(C.point_neg(a_pt), splits=PK_SPLITS)
    return jnp.transpose(tabs, (4, 0, 1, 2, 3)).astype(jnp.int16), ok


build_sr_tables_split = jax.jit(build_sr_tables_split_impl)


def verify_sr_kernel_cached_split_impl(tables, oks, slots, r_enc, s_bytes, k_bytes):
    """Cache-hit kernel on the split ladder. The split ladder's output
    carries no T, but ristretto encode reads it — adding the identity
    point regenerates a consistent T in one unified addition (with
    q2 = identity: C = T1*2d*0 = 0 exactly, and the result
    (4XZ : 4YZ : 4Z^2 : 4XY) is projectively q with T3*Z3 == X3*Y3)."""
    from .verify import PK_SPLITS

    r = r_enc.T.astype(jnp.int32)
    s = s_bytes.T.astype(jnp.int32)
    k = k_bytes.T.astype(jnp.int32)
    a_tables = jnp.transpose(tables[slots].astype(jnp.int32), (1, 2, 3, 4, 0))
    a_ok = oks[slots]
    q = C.double_scalar_mul_split(s, k, a_tables, splits=PK_SPLITS)
    ident = C.identity_point(q.shape[2:]) + 0 * q
    q = C.point_add(q, ident, out_t=True)
    enc = R.encode(q)
    return a_ok & jnp.all(enc == r, axis=0)


verify_sr_kernel_cached_split = jax.jit(verify_sr_kernel_cached_split_impl)

_SR_CACHE = None


def sr_pubkey_cache():
    from .verify import PubkeyCache

    global _SR_CACHE
    if _SR_CACHE is None:
        _SR_CACHE = PubkeyCache(build_fn=build_sr_tables_split, plane="sr25519_pk")
    return _SR_CACHE


def prepare_batch(pubkeys, msgs, sigs):
    """Host prep: (a_enc, r_enc, s_bytes, k_bytes, precheck) uint8/bool
    arrays of shape (B, 32)/(B,). Malformed inputs fail precheck.
    Merlin challenges run through the vectorized batch transcript
    (crypto/merlin_batch.py) so host prep keeps pace with the chip."""
    from ..crypto.sr25519 import SIG_SIZE, challenges_batch

    n = len(sigs)
    raw = np.zeros((4, n, 32), np.uint8)
    precheck = np.zeros((n,), bool)
    for i in range(n):
        pk, sig = pubkeys[i], sigs[i]
        if len(pk) != 32 or len(sig) != SIG_SIZE or not sig[63] & 0x80:
            continue
        s_buf = bytearray(sig[32:64])
        s_buf[31] &= 0x7F
        if int.from_bytes(bytes(s_buf), "little") >= L:
            continue
        raw[0, i] = np.frombuffer(pk, np.uint8)
        raw[1, i] = np.frombuffer(sig, np.uint8, count=32)
        raw[2, i] = np.frombuffer(bytes(s_buf), np.uint8)
        precheck[i] = True
    valid = np.flatnonzero(precheck)
    if len(valid):
        ks = challenges_batch(
            [pubkeys[i] for i in valid],
            [msgs[i] for i in valid],
            [sigs[i][:32] for i in valid],
        )
        for i, k in zip(valid, ks):
            raw[3, i] = np.frombuffer(k.to_bytes(32, "little"), np.uint8)
    return raw[0], raw[1], raw[2], raw[3], precheck


def verify_batch_async(pubkeys, msgs, sigs):
    """Dispatch one batch without blocking (host prep + H2D + launch),
    returning (device_bitmap, precheck, n, flow) — same pipelining
    contract as the ed25519 plane (ops/verify.py verify_batch_async)."""
    from .. import devobs as _devobs
    from .verify import _pad_pow2, pad_pow2_rows

    n = len(sigs)
    if n == 0:
        return None, np.zeros((0,), bool), 0, 0
    fid = _devobs.next_flow() if _devobs.enabled() else 0
    a, r, s, k, precheck = prepare_batch(pubkeys, msgs, sigs)
    a, r, s, k = pad_pow2_rows([a, r, s, k], n)
    with _devobs.transfer_span("h2d", a.nbytes + r.nbytes + s.nbytes + k.nbytes, flow=fid):
        a_dev, r_dev, s_dev, k_dev = (
            jnp.asarray(a), jnp.asarray(r), jnp.asarray(s), jnp.asarray(k)
        )
    with _devobs.attribution(fn="sr25519_bitmap", rows=_pad_pow2(n), flow=fid):
        ok_dev = verify_sr_kernel(a_dev, r_dev, s_dev, k_dev)
    launched.note("sr25519", _pad_pow2(n))
    return ok_dev, precheck, n, fid


def verify_batch_cached_async(pubkeys, msgs, sigs):
    """verify_batch_async through the HBM ristretto-table cache (same
    contract as the ed25519 plane's verify_batch_cached_async)."""
    from .verify import dispatch_cached

    return dispatch_cached(
        sr_pubkey_cache(), prepare_batch, verify_sr_kernel_cached_split,
        verify_batch_async, pubkeys, msgs, sigs,
        fn_label="sr25519_bitmap_cached",
    )


def verify_batch_cached(pubkeys, msgs, sigs) -> np.ndarray:
    """End-to-end cached sr25519 verification -> (n,) bool bitmap."""
    return collect(verify_batch_cached_async(pubkeys, msgs, sigs))


def collect(dispatched) -> np.ndarray:
    """Block on a verify_batch_async result and fold in the precheck."""
    from .verify import read_back

    ok_dev, precheck, n = dispatched[:3]
    if n == 0:
        return np.zeros((0,), bool)
    fid = dispatched[3] if len(dispatched) > 3 else 0
    return read_back(ok_dev, n, fid)[:n] & precheck


def verify_batch(pubkeys, msgs, sigs) -> np.ndarray:
    """End-to-end batched sr25519 verification -> (n,) bool bitmap."""
    return collect(verify_batch_async(pubkeys, msgs, sigs))
