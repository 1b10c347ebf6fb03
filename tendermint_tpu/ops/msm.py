"""Randomized-linear-combination batched ed25519 verification (MSM).

The reference's batch-perf trick is one randomized linear combination

    [8](-[sum z_i s_i mod L]B + sum [z_i]R_i + sum [z_i h_i mod L]A_i) == 0

with per-batch random 128-bit z_i — curve25519-voi behind
BatchVerifier.Verify (ref: crypto/ed25519/ed25519.go:225-233): ONE
multi-scalar multiplication whose doublings are shared across all k
signatures. This module is the TPU-native formulation of that equation;
the per-signature bitmap kernel (ops/verify.py) remains the
localization fallback, giving the same two-phase shape the reference
uses (batch first, re-verify on failure, types/validation.go:245-255).

TPU-native MSM design (no scatter, no sort, static shapes):
  - Per signature two points enter the sum: -R_i with the 128-bit
    scalar z_i (32 nibbles) and -A_i with z_i*h_i mod L (64 nibbles);
    [sum z_i s_i]B rides the host-precomputed fixed-base comb.
  - Window-parallel Straus accumulation: G point-streams run in
    parallel (lanes); each round builds the 16-multiples tables of the
    next G points of A and R in one width-2G pass, then accumulates
    each point's windowed table entries into the per-(window, stream)
    accumulator W with ONE point_add at width 64*G (all windows in
    parallel) — doublings are deferred entirely to the tail.
  - Tail: Horner-combine W over windows (4 doublings + 1 add per
    nibble, at width G), tree-reduce the G streams, add [zs]B, clear
    the cofactor, test the identity. O(windows * G) work amortized to
    nothing by B >= G.

Per-signature cost: ~126 point additions and ~0 doublings, vs ~126
additions + 252 doublings for the per-signature ladder — the same
doubling amortization the reference's RLC gets, reached by windowing
across VPU lanes instead of a serial Pippenger.

Acceptance: all-valid batches accept deterministically (a sum of
per-signature identities is the identity); any invalid signature makes
the check fail except with probability ~2^-128 over z (the reference's
own soundness bound), upon which the caller re-verifies with the
bitmap kernel — so end-to-end acceptance stays byte-identical to the
per-signature plane.
"""

from __future__ import annotations

import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import curve as C
from . import launched
from .. import devobs as _devobs
from .. import trace as _trace
from ..metrics import engine_metrics as _engine_metrics
from .verify import L, _pad_pow2, pad_pow2_rows, prepare_batch

# Parallel point-streams. 128 fills the VPU lane axis for the table
# builds; the accumulate add then runs at width 64*G. Batches smaller
# than G fall back to G=B (the pad floor is 8). A power of two: padded
# batches are powers of two (pad_pow2_rows, floor 8), so G always
# divides the batch exactly — a non-divisor would silently truncate
# rounds and drop signatures from the sum (_accumulate_windows raises).
G_STREAMS = 128


def _select_windows(table, nibs):
    """table: (16, 4, 32, G); nibs: (W, G) -> (4, 32, W, G) windowed
    entries via one-hot multiply-accumulate (gather-free)."""
    oh = (nibs[None] == jnp.arange(16, dtype=jnp.int32)[:, None, None]).astype(jnp.int32)
    # (16,1,1,1,G dims) align: table[:, :, :, None, :] * oh[:, None, None, :, :]
    return jnp.sum(table[:, :, :, None, :] * oh[:, None, None, :, :], axis=0)


def _tree_reduce_points(p):
    """Sum a (4, 32, G) stack of points down to (4, 32, 1)."""
    g = p.shape[-1]
    while g > 1:
        half = g // 2
        p = C.point_add(p[..., :half], p[..., half : 2 * half], out_t=True)
        g = half
    return p


def _accumulate_windows(neg, nibs_zk, nibs_z, n):
    """Shared window-parallel Straus accumulation + Horner + stream
    reduce for both signature planes: neg holds the stacked negated
    points (-A | -R, shape (4, 32, 2n)); returns the (4, 32, 1) total
    of sum zk_i*(-A_i) + z_i*(-R_i) with a valid T coordinate."""
    g = min(G_STREAMS, n)
    if n % g:
        # Trace-time guard (n and g are static shapes): rounds = n // g
        # would silently DROP the tail rows from the RLC sum — a tail
        # row holding the only invalid signature would be excluded and
        # the batch falsely accepted. In-repo dispatchers pad to power-
        # of-two sizes so this never fires for them; a direct caller
        # must fail loudly, not truncate.
        raise ValueError(
            f"MSM batch size {n} is not a multiple of the stream count {g}; "
            f"pad the batch (pad_pow2_rows) so no rows drop from the RLC sum"
        )
    rounds = n // g
    w0 = C.identity_point((64, g)) + 0 * neg[:, :, :1, None]  # vma tie

    def round_body(t, w_acc):
        col_a = lax.dynamic_slice_in_dim(neg, t * g, g, axis=2)
        col_r = lax.dynamic_slice_in_dim(neg, n + t * g, g, axis=2)
        tables = C._build_var_table(jnp.concatenate([col_a, col_r], axis=2))
        d_a = lax.dynamic_slice_in_dim(nibs_zk, t * g, g, axis=1)  # (64, g)
        d_r = lax.dynamic_slice_in_dim(nibs_z, t * g, g, axis=1)  # (32, g)
        entry_a = _select_windows(tables[..., :g], d_a)  # (4,32,64,g)
        entry_r = _select_windows(tables[..., g:], d_r)  # (4,32,32,g)
        w_acc = C.point_add(w_acc, entry_a, out_t=True)
        lo = C.point_add(w_acc[:, :, :32], entry_r, out_t=True)
        return jnp.concatenate([lo, w_acc[:, :, 32:]], axis=2)

    w_acc = lax.fori_loop(0, rounds, round_body, w0)

    def horner_step(i, acc):
        acc = C.point_double(acc, out_t=False)
        acc = C.point_double(acc, out_t=False)
        acc = C.point_double(acc, out_t=False)
        acc = C.point_double(acc, out_t=True)
        wth = lax.dynamic_index_in_dim(w_acc, 62 - i, axis=2, keepdims=False)
        return C.point_add(acc, wth, out_t=True)

    acc = lax.fori_loop(0, 63, horner_step, w_acc[:, :, 63])
    return _tree_reduce_points(acc)


def msm_verify_kernel_impl(a_enc, r_enc, zk_bytes, z_bytes, zs_bytes):
    """Device kernel: the whole RLC equation in one launch.

    a_enc/r_enc: (B, 32) uint8 encodings; zk_bytes: (B, 32) uint8 with
    z_i*h_i mod L; z_bytes: (B, 16) uint8 with the 128-bit z_i;
    zs_bytes: (1, 32) uint8 with sum z_i s_i mod L. Padding rows carry
    z = zk = 0 (their table entries select the identity) and any
    decodable encoding. Returns a scalar bool: True iff every encoding
    decodes AND the combined equation holds.
    """
    a = a_enc.T.astype(jnp.int32)  # (32, B)
    r = r_enc.T.astype(jnp.int32)
    n = a.shape[1]
    pts, oks = C.decompress(jnp.concatenate([a, r], axis=1), zip215=True)
    neg = C.point_neg(pts)  # -A | -R stacked
    all_ok = jnp.all(oks)

    nibs_zk = C.scalar_to_nibbles(zk_bytes.T.astype(jnp.int32))  # (64, B)
    nibs_z = C.scalar_to_nibbles(z_bytes.T.astype(jnp.int32))  # (32, B)
    total = _accumulate_windows(neg, nibs_zk, nibs_z, n)

    # + [sum z_i s_i]B via the fixed-base comb (64 adds, width 1)
    sb = C.fixed_base_mul(zs_bytes.T.astype(jnp.int32))  # (4, 32, 1)
    total = C.point_add(total, sb, out_t=False)

    # cofactor clear + identity test
    total = lax.fori_loop(0, 3, lambda _, v: C.point_double(v, out_t=False), total)
    return all_ok & C.point_is_identity(total)[0]


msm_verify_kernel = jax.jit(msm_verify_kernel_impl)


def msm_verify_sr_kernel_impl(a_enc, r_enc, zk_bytes, z_bytes, zs_bytes):
    """sr25519/ristretto variant of the RLC check: schnorrkel verifies
    R = [s]B - [c]A, so sum z_i([s_i]B - [c_i]A_i - R_i) must be the
    group identity. ristretto255 is PRIME order — no cofactor clearing,
    and identity is decided by the ristretto ENCODING being the
    32-zero-byte string (projective Edwards equality would miss
    identity-coset representatives). Same window-parallel accumulation
    as the ed25519 kernel; decoding rides the ristretto codec
    (ops/ristretto.py). Padding rows: zero encodings decode to the
    identity, zero scalars select identity table entries."""
    from . import ristretto as R

    a = a_enc.T.astype(jnp.int32)
    r = r_enc.T.astype(jnp.int32)
    n = a.shape[1]
    pts, oks = R.decode(jnp.concatenate([a, r], axis=1))
    neg = C.point_neg(pts)  # -A | -R stacked
    all_ok = jnp.all(oks)

    nibs_zk = C.scalar_to_nibbles(zk_bytes.T.astype(jnp.int32))  # (64, B)
    nibs_z = C.scalar_to_nibbles(z_bytes.T.astype(jnp.int32))  # (32, B)
    total = _accumulate_windows(neg, nibs_zk, nibs_z, n)
    sb = C.fixed_base_mul(zs_bytes.T.astype(jnp.int32))
    total = C.point_add(total, sb, out_t=True)  # ristretto encode reads T
    enc = R.encode(total)  # (32, 1)
    return all_ok & jnp.all(enc == 0)


msm_verify_sr_kernel = jax.jit(msm_verify_sr_kernel_impl)


def verify_batch_rlc_sr_async(pubkeys, msgs, sigs, z_raw: bytes | None = None):
    """sr25519 RLC dispatch (same contract as verify_batch_rlc_async;
    the per-signature sr25519 bitmap kernel is the failure fallback)."""
    from . import verify_sr as VS

    return _dispatch_rlc("sr25519", VS.prepare_batch, msm_verify_sr_kernel, pubkeys, msgs, sigs, z_raw)


def _rlc_scalars_py(s_rows, k_rows, n, z_raw):
    """Pure-Python randomizer math (fallback + oracle for the native
    path): per-signature zk = z*h mod L rows, the z rows, and
    zs = sum z*s mod L."""
    zk = np.zeros((len(k_rows), 32), np.uint8)
    z_out = np.zeros((len(k_rows), 16), np.uint8)
    zs = 0
    from_bytes = int.from_bytes
    for i in range(n):
        z = from_bytes(z_raw[16 * i : 16 * i + 16], "little")
        h = from_bytes(k_rows[i].tobytes(), "little")
        s = from_bytes(s_rows[i].tobytes(), "little")
        zk[i] = np.frombuffer(((z * h) % L).to_bytes(32, "little"), np.uint8)
        z_out[i] = np.frombuffer(z.to_bytes(16, "little"), np.uint8)
        zs = (zs + z * s) % L
    zs_row = np.frombuffer(zs.to_bytes(32, "little"), np.uint8).reshape(1, 32)
    return zk, z_out, zs_row


def _rlc_scalars(s_rows, k_rows, n, z_raw):
    """Host-side randomizer math; native C when available (prep.c
    tm_rlc_scalars — the Python loop tops out ~280k sigs/s, below the
    chip's appetite). s_rows/k_rows are (B, 32) uint8 from
    prepare_batch (only the first n rows are real jobs)."""
    from ..native import load_prep

    lib = load_prep()
    if lib is None:
        return _rlc_scalars_py(s_rows, k_rows, n, z_raw)
    import ctypes

    zk = np.zeros((len(k_rows), 32), np.uint8)
    zs_row = np.zeros((1, 32), np.uint8)
    s_c = np.ascontiguousarray(s_rows[:n])
    k_c = np.ascontiguousarray(k_rows[:n])
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.tm_rlc_scalars(
        bytes(z_raw[: 16 * n]),
        s_c.ctypes.data_as(u8p),
        k_c.ctypes.data_as(u8p),
        n,
        zk.ctypes.data_as(u8p),
        zs_row.ctypes.data_as(u8p),
    )
    z_out = np.zeros((len(k_rows), 16), np.uint8)
    z_out[:n] = np.frombuffer(z_raw[: 16 * n], np.uint8).reshape(n, 16)
    return zk, z_out, zs_row


def _ensure_z_raw(n: int, z_raw: bytes | None) -> bytes:
    """Sample (or validate) the per-batch randomizers. A zero z_i would
    null that signature's contribution (false accept) — regenerate, hit
    with probability ~n * 2^-128. A short caller-supplied buffer would
    yield z_i = 0 for the tail rows, silently excluding them."""
    if z_raw is None:
        z_raw = os.urandom(16 * n)
        while any(
            z_raw[16 * i : 16 * i + 16] == b"\x00" * 16 for i in range(n)
        ):  # pragma: no cover
            z_raw = os.urandom(16 * n)
    elif len(z_raw) != 16 * n:
        raise ValueError(f"z_raw must be {16 * n} bytes, got {len(z_raw)}")
    return z_raw


def _prep_rlc(prepare, pubkeys, msgs, sigs, n):
    """`ops.prep`: the plane's host prep (native/prep.c when built) and
    its precheck. None when a row is malformed: the RLC path refuses
    the batch and the caller's bitmap plane localizes."""
    with _trace.span("ops.prep", "ops", rows=n):
        a_enc, r_enc, s_rows, k_rows, precheck = prepare(pubkeys, msgs, sigs)
        refused = not precheck.all()
    if refused:
        _trace.annotate(refused="precheck")  # on the dispatch span, the innermost open
        return None
    return a_enc, r_enc, s_rows, k_rows


def _scalars_rlc(s_rows, k_rows, n, z_raw):
    """`ops.rlc_scalars`: the randomizer draw and the scalar arithmetic."""
    with _trace.span("ops.rlc_scalars", "ops", rows=n):
        z_raw = _ensure_z_raw(n, z_raw)
        return _rlc_scalars(s_rows, k_rows, n, z_raw)


def _launch_rlc(kernel, rows, zs_row, n, fid):
    """`ops.launch`: pad the per-row arrays to the program's row count,
    stage them (`device.h2d`, its child) and make the asynchronous
    kernel call (and a compile, when one happens)."""
    padded = _pad_pow2(n)
    with _trace.span("ops.launch", "ops", rows=n, padded=padded):
        rows = pad_pow2_rows(rows, n)
        nbytes = sum(a.nbytes for a in rows) + zs_row.nbytes
        with _devobs.transfer_span("h2d", nbytes, flow=fid):
            dev_args = [jnp.asarray(a) for a in (*rows, zs_row)]
        with _devobs.attribution(fn="rlc", rows=padded, flow=fid):
            return kernel(*dev_args)


def _dispatch_rlc(plane, prepare, kernel, pubkeys, msgs, sigs, z_raw):
    """Shared RLC dispatch for both signature planes: prep, precheck
    refusal (None -> caller goes straight to its bitmap plane, exactly
    like the reference's early return on AddWithError), randomizer
    math, pow2 padding, kernel launch."""
    n = len(sigs)
    if n == 0:
        return None
    fid = _devobs.next_flow() if _devobs.enabled() else 0
    with _trace.span("ops.msm_dispatch", "ops", kernel="rlc", rows=n, flow=fid):
        prepped = _prep_rlc(prepare, pubkeys, msgs, sigs, n)
        if prepped is None:
            return None
        a_enc, r_enc, s_rows, k_rows = prepped
        zk, z_out, zs_row = _scalars_rlc(s_rows, k_rows, n, z_raw)
        handle = _launch_rlc(kernel, [a_enc, r_enc, zk, z_out], zs_row, n, fid)
    launched.note(plane, _pad_pow2(n))
    _engine_metrics().kernel_launches.add(1, "rlc")
    return handle


def verify_batch_rlc_async(pubkeys, msgs, sigs, z_raw: bytes | None = None):
    """Dispatch the ed25519 RLC check without blocking. Returns an
    opaque handle for collect_rlc, or None on precheck refusal."""
    return _dispatch_rlc("ed25519", prepare_batch, msm_verify_kernel, pubkeys, msgs, sigs, z_raw)


def collect_rlc(dispatched) -> bool:
    """Block on a verify_batch_rlc_async handle -> all-valid bool."""
    if dispatched is None:
        return False
    # the wait for the kernel apart from the read-back: device.d2h
    # times the transfer alone
    with _trace.span("device.wait", "device"):
        dispatched.block_until_ready()
    with _devobs.transfer_span("d2h", int(getattr(dispatched, "nbytes", 1) or 1)):
        return bool(dispatched)


def verify_batch_rlc(pubkeys, msgs, sigs, z_raw: bytes | None = None) -> bool:
    """End-to-end RLC check: True iff EVERY signature is valid (then the
    bitmap is all-ones by construction); False means at least one bad
    signature w.h.p. — localize with ops/verify.verify_batch."""
    return collect_rlc(verify_batch_rlc_async(pubkeys, msgs, sigs, z_raw))
