"""Batched ed25519 verification (the north-star kernel).

Replaces curve25519-voi's randomized batch equation
(ref: crypto/ed25519/ed25519.go:198-233) with a TPU-native design: every
signature's cofactored ZIP-215 equation

    [8]([s]B - [k]A - R) == identity,  k = SHA512(R || A || M) mod L

is evaluated data-parallel across the batch. This is deterministic (no
Z-randomizers), yields the per-signature validity bitmap directly (the
reference needs a serial re-verify pass to find bad indices —
types/validation.go:245-255), and accepts exactly the same signatures.

Split of labor:
  host   — SHA-512 challenges (cheap vs curve math), s < L range check,
           input shaping/padding
  device — point decompression (A and R in one stacked pass), the joint
           [s]B + [k](-A) Straus ladder with shared doublings, then the
           cofactored equation as a projective equality
           [8]([s]B - [k]A) == [8]R (both sides doubled in one stacked
           scanned loop, compared by cross-multiplication): one fused
           XLA program with the batch on the VPU lane axis throughout
"""

from __future__ import annotations

import hashlib
import time

import numpy as np

import jax
import jax.numpy as jnp

from . import curve as C
from . import launched
from .launched import pad_pow2 as _pad_pow2
from .. import devobs as _devobs
from .. import trace as _trace
from ..metrics import engine_metrics as _engine_metrics

L = 2**252 + 27742317777372353535851937790883648493


def _cofactored_accept(q, r_pt, a_ok, r_ok, n):
    """Shared acceptance tail: the ZIP-215 equation
    [8]([s]B - [k]A - R) == identity restated as the projective equality
    [8]([s]B - [k]A) == [8]R — the subtraction (which would need the
    ladder's T and an unrolled final window) becomes a cross-multiplied
    equality, and the cofactor doublings of both sides run stacked in
    one loop. Used by every verify kernel so the accepted set can never
    fork between the uncached/cached/split planes."""
    both = jnp.concatenate([q, r_pt], axis=-1)  # (4, 32, 2B)
    both = jax.lax.fori_loop(
        0, 3, lambda _, v: C.point_double(v, out_t=False), both
    )
    return a_ok & r_ok & C.point_equal(both[..., :n], both[..., n:])


def verify_kernel_impl(a_enc, r_enc, s_bytes, k_bytes):
    """Device kernel: (B, 32) int32 byte arrays -> (B,) bool validity.

    a_enc/r_enc are raw encodings (ZIP-215 decoding on device); s_bytes
    must be pre-checked < L on host; k_bytes is the SHA-512 challenge
    already reduced mod L. Inputs arrive batch-major (the natural host
    and sharding layout) and are transposed on device to the limb-major
    layout the field kernels want (ops/field.py).
    """
    # Accept uint8 (the transfer format: 4x fewer bytes over PCIe
    # than int32) and widen on device where the cast is free.
    a = a_enc.T.astype(jnp.int32)  # (32, B)
    r = r_enc.T.astype(jnp.int32)
    s = s_bytes.T.astype(jnp.int32)
    k = k_bytes.T.astype(jnp.int32)
    n = a.shape[1]
    pts, oks = C.decompress(jnp.concatenate([a, r], axis=1), zip215=True)
    a_pt, r_pt = pts[..., :n], pts[..., n:]
    a_ok, r_ok = oks[:n], oks[n:]
    q = C.double_scalar_mul_base(s, k, C.point_neg(a_pt), final_t=False)
    return _cofactored_accept(q, r_pt, a_ok, r_ok, n)


verify_kernel = jax.jit(verify_kernel_impl)


# Split-ladder cached plane: the HBM cache stores power-of-2^(256/S)
# multiples tables of each negated pubkey, so the cache-hit ladder needs
# only 256/S/4*4 - 4 shared doublings instead of 252 (doublings are
# ~45% of the kernel; at S=4 this removes ~40% of the per-sig field
# work). [s]B rides rows of the host-precomputed fixed-base comb, which
# never needed doublings at all.
PK_SPLITS = 4


def build_pk_tables_split_impl(a_enc):
    """Cache-fill kernel for the split plane: (B, 32) pubkey encodings ->
    (B, S, 16, 4, 32) int16 power-multiples tables of the negated
    points + (B,) decode-ok bits. The (S-1)*(256/S) doubling chains run
    once here, then never again for this key."""
    a = a_enc.T.astype(jnp.int32)
    a_pt, ok = C.decompress(a, zip215=True)
    tabs = C.build_power_tables(C.point_neg(a_pt), splits=PK_SPLITS)
    return jnp.transpose(tabs, (4, 0, 1, 2, 3)).astype(jnp.int16), ok


build_pk_tables_split = jax.jit(build_pk_tables_split_impl)


def verify_kernel_cached_split_impl(tables, oks, slots, r_enc, s_bytes, k_bytes):
    """Cache-hit kernel on the split ladder (see double_scalar_mul_split)."""
    r = r_enc.T.astype(jnp.int32)
    s = s_bytes.T.astype(jnp.int32)
    k = k_bytes.T.astype(jnp.int32)
    n = r.shape[1]
    a_tables = jnp.transpose(tables[slots].astype(jnp.int32), (1, 2, 3, 4, 0))
    a_ok = oks[slots]
    r_pt, r_ok = C.decompress(r, zip215=True)
    q = C.double_scalar_mul_split(s, k, a_tables, splits=PK_SPLITS)
    return _cofactored_accept(q, r_pt, a_ok, r_ok, n)


verify_kernel_cached_split = jax.jit(verify_kernel_cached_split_impl)


@jax.jit
def _publish_pk_tables(tables, oks, idx, new_tables, new_oks):
    """A fill's rows into the cache's arrays, as NEW arrays (in-flight
    batches keep the ones they were dispatched with). One program per
    padded row count: rows whose index is out of range are dropped."""
    return (tables.at[idx].set(new_tables, mode="drop"),
            oks.at[idx].set(new_oks, mode="drop"))


class PubkeyCache:
    """HBM-resident decompressed-pubkey cache (the device analog of the
    reference's 4096-entry expanded-pubkey LRU, crypto/ed25519/
    ed25519.go:57). Stores each pubkey's PK_SPLITS power tables of the
    negated point (build_pk_tables_split) so cache hits skip
    decompression AND the table build, take the short split ladder, and
    never re-send A bytes through the host link.

    Functional-update safety: eviction overwrites slots via .at[].set,
    which creates a NEW device array — in-flight async batches keep
    referencing the buffers they were dispatched with."""

    def __init__(self, capacity: int = 4096, build_fn=None, plane: str = "pk", sharding=None,
                 fill_rows: int | None = None):
        import collections
        import threading

        self.capacity = capacity
        # the rows a fill's two programs run at: the launch bucket of the
        # batch that missed, or one fixed count (at most `capacity`)
        self.fill_rows = fill_rows
        # where the arrays live: the default device, or (the sharded
        # route's cache) every chip of a mesh, replicated, so that a
        # launch reads its rows' tables on its own chip
        self._put = jnp.asarray if sharding is None else (
            lambda x: jax.device_put(x, sharding))
        self.plane = plane  # devobs compile-attribution + residency label
        # the signature plane a fill's programs are noted under (ops/launched.py)
        self.signature_plane = plane.removesuffix("_pk")
        self._build = build_fn or build_pk_tables_split  # sr25519 plugs in its decoder
        self._lock = threading.Lock()  # reactors verify concurrently
        self._lru: "collections.OrderedDict[bytes, int]" = collections.OrderedDict()
        # Two-phase fill bookkeeping. The table build is a device
        # kernel launch — held across the lock it serialized every
        # concurrent verifier behind one miss fill (tmcheck hold_budget
        # found it at 1.5s under CPU emulation), so fills reserve under
        # the lock, build unlocked, and publish under the lock.
        #   _pending: keys whose table is RESERVED but not yet
        #   published (key -> Event set at publish) — other batches
        #   touching them must wait, so no caller ever reads an
        #   unpublished slot.
        #   _pinned: eviction pin-COUNTS for every key an in-flight
        #   fill batch depends on, hits included — their slots must
        #   survive until the filler's publish-time snapshot, but their
        #   published tables stay freely readable by concurrent
        #   batches (a hot validator key shared with a fill must not
        #   re-serialize hit-only verifiers behind the build).
        self._pending: "dict[bytes, threading.Event]" = {}
        self._pinned: "dict[bytes, int]" = {}
        self.tables = self._put(jnp.zeros((capacity, PK_SPLITS, 16, 4, 32), jnp.int16))
        self.oks = self._put(jnp.zeros((capacity,), bool))

    def ensure(self, pubkeys):
        """Map pubkeys -> slot indices, inserting misses in one batched
        device call. Returns (B,) int32 slots, or None when the batch
        has more distinct keys than the cache holds (caller falls back
        to the uncached kernel)."""
        slots, _tables, _oks = self.ensure_snapshot(pubkeys)
        return slots

    def ensure_snapshot(self, pubkeys):
        """(slots, tables, oks) as ONE consistent view: the returned
        arrays are the ones the slot computation published against
        (functional .at[].set updates are lock-free to USE but not to
        publish). Miss fills build their tables with the lock RELEASED
        — concurrent batches over cached keys proceed immediately, and
        disjoint miss batches fill in parallel."""
        import threading

        while True:
            with self._lock:
                distinct = list(dict.fromkeys(pubkeys))
                if len(distinct) > self.capacity:
                    self._count(len(pubkeys), len(pubkeys))
                    return None, self.tables, self.oks
                waits = {
                    self._pending[pk] for pk in distinct if pk in self._pending
                }
                if waits:
                    pass  # another thread is filling keys we need
                else:
                    # Refresh present keys FIRST so eviction below can
                    # never pop a key this very batch is about to use.
                    for pk in distinct:
                        if pk in self._lru:
                            self._lru.move_to_end(pk)
                    missing = [pk for pk in distinct if pk not in self._lru]
                    if not missing:
                        slots = np.fromiter(
                            (self._lru[pk] for pk in pubkeys), np.int32
                        )
                        self._count(len(pubkeys), 0)
                        return slots, self.tables, self.oks
                    free = self.capacity - len(self._lru)
                    evictable = [
                        pk for pk in self._lru
                        if pk not in self._pending and pk not in self._pinned
                    ]  # OrderedDict order = least-recent first
                    need = max(0, len(missing) - free)
                    if need > len(evictable):
                        # every eviction candidate is mid-fill by other
                        # threads: fall back to the uncached kernel
                        # instead of waiting on unrelated fills
                        self._count(len(pubkeys), len(pubkeys))
                        return None, self.tables, self.oks
                    for pk in evictable[:need]:
                        del self._lru[pk]
                    used = set(self._lru.values())
                    free_slots = iter(
                        i for i in range(self.capacity) if i not in used
                    )
                    idx = np.fromiter(
                        (next(free_slots) for _ in missing), np.int32
                    )
                    # Reserve: missing keys become pending (waiters
                    # park until publish); EVERY key of the batch —
                    # hits included — takes an eviction pin so its
                    # slot survives until our publish-time snapshot.
                    event = threading.Event()
                    for pk, slot in zip(missing, idx):
                        self._lru[pk] = int(slot)
                        self._pending[pk] = event
                    for pk in distinct:
                        self._pinned[pk] = self._pinned.get(pk, 0) + 1
            if waits:
                for ev in waits:
                    ev.wait()
                continue  # retry: the fills we waited on moved the LRU
            # ---- build OUTSIDE the lock (the expensive device call)
            # Both programs of a fill run at the launch bucket of the
            # batch that missed (or at `fill_rows`), whatever the number
            # of misses: the rows past the misses decode a zero key and
            # scatter to slot `capacity`, out of range, which the
            # publish drops. A rotating validator set misses 1, 3, 13
            # keys of a batch; shapes cut to the miss count compiled
            # inside each update.
            m, rows = len(missing), self.fill_rows or _pad_pow2(len(pubkeys))
            t0 = time.perf_counter()
            try:
                enc_p = np.zeros((rows, 32), np.uint8)
                enc_p[:m] = np.frombuffer(b"".join(missing), np.uint8).reshape(-1, 32)
                idx_p = np.full((rows,), self.capacity, np.int32)
                idx_p[:m] = idx
                fid = _devobs.next_flow() if _devobs.enabled() else 0
                with _trace.span("ops.pk_cache_fill", "ops", misses=m, rows=rows, flow=fid):
                    with _devobs.transfer_span("h2d", enc_p.nbytes + idx_p.nbytes, flow=fid):
                        enc_dev, idx_dev = self._put(enc_p), self._put(idx_p)
                    with _devobs.attribution(
                        fn=f"{self.plane}_table_build", rows=rows, flow=fid,
                    ):
                        new_tables, new_oks = self._build(enc_dev)
                _engine_metrics().kernel_launches.add(1, "pk_table_build")
            except BaseException:
                with self._lock:
                    for pk in missing:
                        self._lru.pop(pk, None)
                        if self._pending.get(pk) is event:
                            del self._pending[pk]
                    self._unpin(distinct)
                event.set()  # waiters retry against the rolled-back state
                raise
            with self._lock:
                with _devobs.attribution(fn=f"{self.plane}_table_publish", rows=rows, flow=fid):
                    self.tables, self.oks = _publish_pk_tables(
                        self.tables, self.oks, idx_dev, new_tables, new_oks)
                launched.note(self.signature_plane, rows)
                for pk in missing:
                    if self._pending.get(pk) is event:
                        del self._pending[pk]
                self._unpin(distinct)
                slots = np.fromiter((self._lru[pk] for pk in pubkeys), np.int32)
                tables, oks = self.tables, self.oks
            event.set()
            built = set(missing)
            self._count(len(pubkeys), sum(pk in built for pk in pubkeys))
            self._count_fill(m, rows, time.perf_counter() - t0)
            return slots, tables, oks

    def _count(self, rows: int, missed: int) -> None:
        """One batch's rows looked up, and those of them whose table was
        not on the device (all of them where the batch fell back to the
        uncached kernel): a hit share can be read over any interval."""
        m = _engine_metrics()
        m.pk_cache_rows.add(rows, self.plane)
        m.pk_cache_missed_rows.add(missed, self.plane)

    def _count_fill(self, keys: int, rows: int, seconds: float) -> None:
        """One fill: the keys it built tables for, the padded rows its
        programs ran at, and its wall time, build and publish."""
        m = _engine_metrics()
        m.pk_cache_fills.add(1, self.plane)
        m.pk_cache_filled_keys.add(keys, self.plane)
        m.pk_cache_fill_rows.add(rows, self.plane)
        m.pk_cache_fill_seconds.add(seconds, self.plane)

    def _unpin(self, keys) -> None:
        """Drop one eviction pin per key (lock held by caller)."""
        for pk in keys:
            n = self._pinned.get(pk, 0) - 1
            if n > 0:
                self._pinned[pk] = n
            else:
                self._pinned.pop(pk, None)


_PK_CACHE: PubkeyCache | None = None


def pubkey_cache() -> PubkeyCache:
    global _PK_CACHE
    if _PK_CACHE is None:
        _PK_CACHE = PubkeyCache(plane="ed25519_pk")
    return _PK_CACHE


def pad_pow2_rows(arrays, n: int):
    """Pad (n, 32) uint8 arrays up to the next power-of-two row count so
    jit caches a small set of program shapes (shared by the ed25519 and
    sr25519 planes and the MSM, whose kernels require it)."""
    size = _pad_pow2(n)
    if size == n:
        return arrays
    pad = size - n
    return [np.pad(a, ((0, pad), (0, 0))) for a in arrays]


def _prepare_batch_py(pubkeys, msgs, sigs):
    """Pure-Python prep (fallback + oracle for the native path)."""
    n = len(sigs)
    raw = np.zeros((4, n, 32), np.uint8)  # a, r, s, k rows
    precheck = np.zeros((n,), bool)
    sha512 = hashlib.sha512
    from_bytes = int.from_bytes
    for i in range(n):
        pk, sig = pubkeys[i], sigs[i]
        if len(pk) != 32 or len(sig) != 64:
            continue
        s = from_bytes(sig[32:], "little")
        if s >= L:
            continue
        k = from_bytes(sha512(sig[:32] + pk + msgs[i]).digest(), "little") % L
        raw[0, i] = np.frombuffer(pk, np.uint8)
        raw[1, i] = np.frombuffer(sig, np.uint8, count=32)
        raw[2, i] = np.frombuffer(sig, np.uint8, count=32, offset=32)
        raw[3, i] = np.frombuffer(k.to_bytes(32, "little"), np.uint8)
        precheck[i] = True
    return raw[0], raw[1], raw[2], raw[3], precheck


def _prepare_batch_native(lib, pubkeys, msgs, sigs):
    """C fast path (native/prep.c): one call hashes + reduces + shapes
    the whole batch into uint8 — the host must sustain the chip's
    throughput."""
    import ctypes

    n = len(sigs)
    pks_buf = b"".join(pubkeys)
    sigs_buf = b"".join(sigs)
    msgs_buf = b"".join(msgs)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum([len(m) for m in msgs], out=offsets[1:])
    a = np.zeros((n, 32), np.uint8)
    r = np.zeros((n, 32), np.uint8)
    s = np.zeros((n, 32), np.uint8)
    k = np.zeros((n, 32), np.uint8)
    pre = np.zeros(n, np.uint8)
    as_u8 = lambda arr: arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    lib.prepare_batch(
        pks_buf, sigs_buf, msgs_buf,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
        as_u8(a), as_u8(r), as_u8(s), as_u8(k),
        pre.ctypes.data_as(ctypes.c_char_p),
    )
    return a, r, s, k, pre.astype(bool)


def prepare_batch(pubkeys, msgs, sigs):
    """Host-side shaping: returns (a_enc, r_enc, s_bytes, k_bytes,
    precheck) numpy uint8/bool arrays of shape (B, 32)/(B,) — uint8 is
    the device transfer format (4x fewer bytes than int32; the kernel
    widens on chip). Malformed inputs fail
    precheck instead of raising (callers map them to invalid). Uses the
    native prep library when available (native/prep.c); inputs with
    non-standard lengths take the Python path (the C ABI packs fixed
    32/64-byte keys and sigs)."""
    n = len(sigs)
    if (
        n
        and len(pubkeys) == n
        and len(msgs) == n
        and all(len(pk) == 32 for pk in pubkeys)
        and all(len(sg) == 64 for sg in sigs)
    ):
        from ..native import load_prep

        lib = load_prep()
        if lib is not None:
            return _prepare_batch_native(lib, pubkeys, msgs, sigs)
    return _prepare_batch_py(pubkeys, msgs, sigs)


def verify_batch_async(pubkeys, msgs, sigs):
    """Dispatch one batch without blocking: host prep + uint8 H2D +
    kernel launch, returning (device_bitmap, precheck, n). JAX dispatch
    is asynchronous, so callers can pipeline several batches (the
    transfer of batch i+1 overlaps the compute of batch i) and only pay
    one device round-trip at collection time — the same pipelining the
    reference gets from its socket client (abci/client/socket_client.go:110),
    applied at the host->chip boundary."""
    n = len(sigs)
    if n == 0:
        return None, np.zeros((0,), bool), 0, 0
    fid = _devobs.next_flow() if _devobs.enabled() else 0
    with _trace.span("ops.verify_dispatch", "ops", kernel="bitmap", rows=n, flow=fid):
        with _trace.span("ops.prep", "ops", rows=n):
            a_enc, r_enc, s_bytes, k_bytes, precheck = prepare_batch(pubkeys, msgs, sigs)
        padded = _pad_pow2(n)
        with _trace.span("ops.launch", "ops", rows=n, padded=padded):
            a_enc, r_enc, s_bytes, k_bytes = pad_pow2_rows([a_enc, r_enc, s_bytes, k_bytes], n)
            nbytes = a_enc.nbytes + r_enc.nbytes + s_bytes.nbytes + k_bytes.nbytes
            with _devobs.transfer_span("h2d", nbytes, flow=fid):
                a_dev, r_dev, s_dev, k_dev = (
                    jnp.asarray(a_enc), jnp.asarray(r_enc),
                    jnp.asarray(s_bytes), jnp.asarray(k_bytes),
                )
            with _devobs.attribution(fn="ed25519_bitmap", rows=padded, flow=fid):
                ok_dev = verify_kernel(a_dev, r_dev, s_dev, k_dev)
    launched.note("ed25519", padded)
    _engine_metrics().kernel_launches.add(1, "bitmap")
    return ok_dev, precheck, n, fid


def collect(dispatched) -> np.ndarray:
    """Block on a verify_batch_async result and fold in the precheck."""
    ok_dev, precheck, n = dispatched[:3]
    if n == 0:
        return np.zeros((0,), bool)
    fid = dispatched[3] if len(dispatched) > 3 else 0
    return read_back(ok_dev, n, fid)[:n] & precheck


def read_back(ok_dev, n: int, fid: int) -> np.ndarray:
    """A launch's bitmap on the host (shared by the ed25519 and sr25519
    planes): `device.wait` is the collect thread blocked on the kernel,
    `device.d2h` the read-back alone."""
    with _trace.span("device.wait", "device", flow=fid):
        ok_dev.block_until_ready()
    with _devobs.transfer_span("d2h", int(getattr(ok_dev, "nbytes", n) or n), flow=fid):
        return np.asarray(ok_dev)


def verify_batch(pubkeys, msgs, sigs) -> np.ndarray:
    """End-to-end batched verification. Returns (n,) bool numpy array.

    Batches are padded to the next power of two (with a self-consistent
    dummy job) so jit caches a small set of program shapes.
    """
    return collect(verify_batch_async(pubkeys, msgs, sigs))


def dispatch_cached(cache, prepare, cached_kernel, uncached_async, pubkeys, msgs, sigs,
                    fn_label: str = "bitmap_cached"):
    """Shared cache-path orchestration for both signature planes:
    slot lookup/insert (atomic snapshot), fallback when the batch has
    more distinct keys than the cache, shape padding, kernel dispatch.
    Malformed pubkeys are keyed as zeros — they already fail precheck,
    which masks their lanes at collect; the cache just needs a 32-byte
    key for them."""
    n = len(sigs)
    if n == 0:
        return None, np.zeros((0,), bool), 0, 0
    fid = _devobs.next_flow() if _devobs.enabled() else 0
    with _trace.span("ops.verify_dispatch", "ops", kernel="bitmap_cached", rows=n, flow=fid) as sp:
        with _trace.span("ops.pk_cache_lookup", "ops", rows=n):
            keys = [pk if len(pk) == 32 else b"\x00" * 32 for pk in pubkeys]
            slots, tables, oks = cache.ensure_snapshot(keys)
        if slots is None:
            sp.annotate(cache="overflow")
            return uncached_async(pubkeys, msgs, sigs)
        with _trace.span("ops.prep", "ops", rows=n):
            _, r_enc, s_bytes, k_bytes, precheck = prepare(pubkeys, msgs, sigs)
        padded = _pad_pow2(n)
        with _trace.span("ops.launch", "ops", rows=n, padded=padded):
            r_enc, s_bytes, k_bytes = pad_pow2_rows([r_enc, s_bytes, k_bytes], n)
            slots = np.pad(slots, (0, len(r_enc) - n))
            nbytes = slots.nbytes + r_enc.nbytes + s_bytes.nbytes + k_bytes.nbytes
            with _devobs.transfer_span("h2d", nbytes, flow=fid):
                slots_dev, r_dev, s_dev, k_dev = (
                    jnp.asarray(slots), jnp.asarray(r_enc),
                    jnp.asarray(s_bytes), jnp.asarray(k_bytes),
                )
            with _devobs.attribution(fn=fn_label, rows=padded, flow=fid):
                ok_dev = cached_kernel(tables, oks, slots_dev, r_dev, s_dev, k_dev)
    launched.note(cache.signature_plane, padded)
    _engine_metrics().kernel_launches.add(1, "bitmap_cached")
    return ok_dev, precheck, n, fid


def verify_batch_cached_async(pubkeys, msgs, sigs):
    """verify_batch_async through the HBM pubkey cache: repeated
    validator sets (every production VerifyCommit after the first at a
    given height range) skip A decompression + table build on device."""
    return dispatch_cached(
        pubkey_cache(), prepare_batch, verify_kernel_cached_split,
        verify_batch_async, pubkeys, msgs, sigs,
        fn_label="ed25519_bitmap_cached",
    )


def verify_batch_cached(pubkeys, msgs, sigs) -> np.ndarray:
    """End-to-end cached verification -> (n,) bool bitmap."""
    return collect(verify_batch_cached_async(pubkeys, msgs, sigs))
