"""Sharded batch verification over a device mesh.

The 10k-validator mega-commit path (BASELINE.md config 5): signatures are
sharded along a 1-D mesh axis ("batch"), each chip runs the verification
kernel on its shard with the pubkey table resident in its HBM, and the
all-valid verdict is an AND-reduce over ICI implemented as
`psum(local_fail_count) == 0`.
"""

from __future__ import annotations

import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import devobs as _devobs
from .. import trace as _trace
from ..metrics import engine_metrics as _engine_metrics
from ..ops import verify as V
from ..ops import verify_sr as VS

AXIS = "batch"

# the batch-capable planes (secp256k1 has no batch equation — callers
# fall back to serial host verification, as in the reference)
_PLANES = {
    "ed25519": (V, V.verify_kernel_impl),
    "sr25519": (VS, VS.verify_sr_kernel_impl),
}


def make_mesh(n_devices: int | None = None) -> Mesh:
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (AXIS,))


def _local_verify_with(kernel_impl):
    def _local_verify(a_enc, r_enc, s_bytes, k_bytes):
        ok = kernel_impl(a_enc, r_enc, s_bytes, k_bytes)
        fails = jnp.sum(jnp.where(ok, 0, 1))
        total_fails = jax.lax.psum(fails, AXIS)  # ICI AND-reduce
        return ok, total_fails == 0

    return _local_verify


_FN_CACHE: dict[tuple, object] = {}


def _placement(arrays) -> list[list[int]]:
    """Device ids holding a shard (or replica) of each array, for the
    sharded.verify span: the trace shows where every input and the
    bitmap actually lived."""
    return [sorted(sh.device.id for sh in x.addressable_shards) for x in arrays]

_SCALAR_POOL = None
_SCALAR_POOL_LOCK = threading.Lock()


def _scalar_pool():
    """Shared executor for per-shard RLC scalar prep: one verification
    per commit on the hot sync path must not pay thread create/teardown
    per batch. Idle workers are cheap; the pool lives for the process.
    Locked init — concurrent first callers must not each build (and
    leak) a pool."""
    global _SCALAR_POOL
    if _SCALAR_POOL is None:
        with _SCALAR_POOL_LOCK:
            if _SCALAR_POOL is None:
                from concurrent.futures import ThreadPoolExecutor

                _SCALAR_POOL = ThreadPoolExecutor(
                    max_workers=8, thread_name_prefix="ThreadPoolExecutor-rlc"
                )
    return _SCALAR_POOL


def sharded_verify_fn(mesh: Mesh, kernel_impl=V.verify_kernel_impl):
    """Returns a jitted fn: (B,32)x4 uint8 -> ((B,) bool bitmap sharded
    over the mesh, scalar all-valid replicated). B must divide evenly by
    the mesh size (pad on host). Memoized per (mesh, kernel) so jit's
    trace cache is effective across calls. kernel_impl selects the
    plane: ed25519 (default) or sr25519 (ops/verify_sr.py) — both
    kernels verify their zero-padded rows true by construction."""
    key = (mesh, kernel_impl)
    fn = _FN_CACHE.get(key)
    if fn is None:
        spec = P(AXIS)
        fn = jax.jit(
            shard_map(
                _local_verify_with(kernel_impl),
                mesh=mesh,
                in_specs=(spec, spec, spec, spec),
                out_specs=(spec, P()),
            )
        )
        _FN_CACHE[key] = fn
    return fn


def sharded_cached_verify_fn(mesh: Mesh, kernel_impl):
    """Cached-plane sharded verifier: the HBM tables cache is REPLICATED
    across the mesh (every chip holds the full table array — the
    north-star's 'pubkey table resident in HBM', mesh-wide), while
    slots/r/s/k shard with the batch; each chip gathers its shard's
    table entries locally, so no collective moves table data and the
    verdict stays the one psum AND-reduce."""
    key = (mesh, kernel_impl, "cached")
    fn = _FN_CACHE.get(key)
    if fn is None:
        spec = P(AXIS)

        def local(tables, oks, slots, r_enc, s_bytes, k_bytes):
            ok = kernel_impl(tables, oks, slots, r_enc, s_bytes, k_bytes)
            fails = jnp.sum(jnp.where(ok, 0, 1))
            return ok, jax.lax.psum(fails, AXIS) == 0

        fn = jax.jit(
            shard_map(
                local,
                mesh=mesh,
                in_specs=(P(), P(), spec, spec, spec, spec),
                out_specs=(spec, P()),
            )
        )
        _FN_CACHE[key] = fn
    return fn


def verify_batch_sharded_cached(mesh: Mesh, pubkeys, msgs, sigs, key_type: str = "ed25519"):
    """verify_batch_sharded through the split-ladder HBM cache plane:
    repeat validator sets skip decompression/table build on every chip
    and take the short split ladder. Falls back to the uncached sharded
    path when the batch holds more distinct keys than the cache."""
    n = len(sigs)
    if n == 0:
        return np.zeros((0,), bool), False
    if key_type == "ed25519":
        plane, cache = V, V.pubkey_cache()
        kern = V.verify_kernel_cached_split_impl
    elif key_type == "sr25519":
        plane, cache = VS, VS.sr_pubkey_cache()
        kern = VS.verify_sr_kernel_cached_split_impl
    else:
        raise ValueError(f"unsupported key_type {key_type!r} for sharded verification")
    keys = [pk if len(pk) == 32 else b"\x00" * 32 for pk in pubkeys]
    slots, tables, oks = cache.ensure_snapshot(keys)
    if slots is None:
        return verify_batch_sharded(mesh, pubkeys, msgs, sigs, key_type)
    _engine_metrics().sharded_launches.add(1, "cached")
    with _trace.span("sharded.verify", "parallel", path="cached",
                     rows=n, shards=mesh.devices.size) as sp:
        _, r_enc, s_bytes, k_bytes, precheck = plane.prepare_batch(pubkeys, msgs, sigs)
        n_dev = mesh.devices.size
        per_dev = -(-n // n_dev)
        if per_dev <= 256:
            per_dev = V._pad_pow2(per_dev, floor=8)
        else:
            per_dev = -(-per_dev // 256) * 256
        pad = per_dev * n_dev - n
        if pad:
            r_enc = np.pad(r_enc, ((0, pad), (0, 0)))
            s_bytes = np.pad(s_bytes, ((0, pad), (0, 0)))
            k_bytes = np.pad(k_bytes, ((0, pad), (0, 0)))
        # Pad slots with THIS batch's last slot, not slot 0: padded rows
        # (s = k = 0) verify true against any VALID key's table (the ladder
        # selects only identity entries), and if that key's encoding is
        # invalid its own real row already fails the verdict — whereas
        # slot 0 may hold an unrelated invalid key, failing the psum
        # verdict for an all-valid batch.
        slots = np.pad(slots, (0, pad), mode="edge")
        fn = sharded_cached_verify_fn(mesh, kern)
        shard = NamedSharding(mesh, P(AXIS))
        repl = NamedSharding(mesh, P())
        # host arrays go straight to their shards: jnp.asarray first
        # would commit the whole batch to device 0 and copy from there
        args = [
            jax.device_put(tables, repl),
            jax.device_put(oks, repl),
            jax.device_put(slots, shard),
            jax.device_put(r_enc, shard),
            jax.device_put(s_bytes, shard),
            jax.device_put(k_bytes, shard),
        ]
        with _devobs.attribution(fn=f"{key_type}_sharded_cached", rows=per_dev):
            bitmap, device_all_valid = fn(*args)
        if _trace.enabled():
            sp.annotate(placement=_placement([*args, bitmap]))
        bitmap = np.asarray(bitmap)[:n] & precheck
        return bitmap, bool(device_all_valid) and bool(precheck.all())


def sharded_rlc_fn(mesh: Mesh):
    """Sharded RLC/MSM verifier: each chip evaluates the combined
    equation over ITS shard (any subset of valid signatures sums to the
    identity, so per-shard checks are individually sound) with a
    per-shard zs partial sum, and the global verdict is the same one
    psum AND-reduce as the bitmap plane — MSM sharding needs no point
    collectives at all."""
    from ..ops import msm as M

    key = (mesh, "rlc")
    fn = _FN_CACHE.get(key)
    if fn is None:
        spec = P(AXIS)

        def local(a_enc, r_enc, zk, z, zs_row):
            ok = M.msm_verify_kernel_impl(a_enc, r_enc, zk, z, zs_row)
            return jax.lax.psum(jnp.where(ok, 0, 1), AXIS) == 0

        fn = jax.jit(
            shard_map(
                local,
                mesh=mesh,
                in_specs=(spec, spec, spec, spec, spec),
                out_specs=P(),
            )
        )
        _FN_CACHE[key] = fn
    return fn


def verify_batch_sharded_rlc(mesh: Mesh, pubkeys, msgs, sigs, z_raw: bytes | None = None):
    """All-valid fast path over the mesh: True iff every signature is
    valid (deterministic for valid sets); False directs the caller to a
    bitmap plane for localization (verify_batch_sharded), mirroring the
    single-chip two-phase dispatch. ed25519 only — sr25519's RLC plane
    would need its own challenge transcripting."""
    from ..ops import msm as M

    n = len(sigs)
    if n == 0:
        return False
    a_enc, r_enc, s_rows, k_rows, precheck = V.prepare_batch(pubkeys, msgs, sigs)
    if not precheck.all():
        return False
    _engine_metrics().sharded_launches.add(1, "rlc")
    z_raw = M._ensure_z_raw(n, z_raw)
    n_dev = mesh.devices.size
    per_dev = -(-n // n_dev)
    if per_dev <= 256:
        per_dev = V._pad_pow2(per_dev, floor=8)
    else:
        per_dev = -(-per_dev // 256) * 256
    size = per_dev * n_dev
    # per-shard scalar math: one native _rlc_scalars call per shard
    # slice yields that shard's zk rows AND its zs partial sum directly
    # (shard d's equation covers exactly its own rows). Shards run on a
    # thread pool: the native call is a ctypes FFI that releases the
    # GIL, so per-shard prep scales across cores instead of serializing
    # the device feed behind one Python loop.
    zk = np.zeros((size, 32), np.uint8)
    z_rows = np.zeros((size, 16), np.uint8)
    zs_shards = np.zeros((n_dev, 32), np.uint8)

    def shard_scalars(d):
        lo, hi = d * per_dev, min((d + 1) * per_dev, n)
        zk_d, z_d, zs_d = M._rlc_scalars(
            s_rows[lo:hi], k_rows[lo:hi], hi - lo, z_raw[16 * lo : 16 * hi]
        )
        zk[lo:hi] = zk_d
        z_rows[lo:hi] = z_d
        zs_shards[d] = zs_d[0]

    live = [d for d in range(n_dev) if d * per_dev < n]
    if len(live) > 1:
        # list() propagates the first worker exception, if any
        list(_scalar_pool().map(shard_scalars, live))
    else:
        for d in live:
            shard_scalars(d)
    pad = size - n
    if pad:
        a_enc = np.pad(a_enc, ((0, pad), (0, 0)))
        r_enc = np.pad(r_enc, ((0, pad), (0, 0)))
    fn = sharded_rlc_fn(mesh)
    sharding = NamedSharding(mesh, P(AXIS))
    args = [jax.device_put(x, sharding) for x in (a_enc, r_enc, zk, z_rows, zs_shards)]
    with _trace.span("sharded.verify", "parallel", path="rlc",
                     rows=n, shards=n_dev) as sp:
        if _trace.enabled():
            sp.annotate(placement=_placement(args))
        with _devobs.attribution(fn="ed25519_sharded_rlc", rows=per_dev):
            return bool(fn(*args))


def verify_batch_sharded(mesh: Mesh, pubkeys, msgs, sigs, key_type: str = "ed25519"):
    """Host glue mirroring ops.verify.verify_batch but sharded. Returns
    (bitmap numpy (n,), all_valid bool). key_type selects the plane:
    both of the batch-capable key types shard the same way."""
    n = len(sigs)
    if n == 0:
        return np.zeros((0,), bool), False
    try:
        plane, kernel_impl = _PLANES[key_type]
    except KeyError:
        raise ValueError(
            f"unsupported key_type {key_type!r} for sharded verification "
            f"(batch-capable: {sorted(_PLANES)})"
        ) from None
    _engine_metrics().sharded_launches.add(1, "bitmap")
    a_enc, r_enc, s_bytes, k_bytes, precheck = plane.prepare_batch(pubkeys, msgs, sigs)
    n_dev = mesh.devices.size
    # Shard-size schedule: powers of two up to 256 per device, then
    # 256-multiples — a bounded jit-shape zoo with at most ~2.5% padding
    # waste at the 10k scale (pure pow2 padding would waste 63% there:
    # 10000 -> 16384).
    per_dev = -(-n // n_dev)
    if per_dev <= 256:
        per_dev = V._pad_pow2(per_dev, floor=8)
    else:
        per_dev = -(-per_dev // 256) * 256
    size = per_dev * n_dev
    pad = size - n
    if pad:
        a_enc = np.pad(a_enc, ((0, pad), (0, 0)))
        r_enc = np.pad(r_enc, ((0, pad), (0, 0)))
        s_bytes = np.pad(s_bytes, ((0, pad), (0, 0)))
        k_bytes = np.pad(k_bytes, ((0, pad), (0, 0)))
    fn = sharded_verify_fn(mesh, kernel_impl)
    sharding = NamedSharding(mesh, P(AXIS))
    args = [jax.device_put(x, sharding) for x in (a_enc, r_enc, s_bytes, k_bytes)]
    with _trace.span("sharded.verify", "parallel", path="bitmap",
                     rows=n, shards=n_dev) as sp:
        with _devobs.attribution(fn=f"{key_type}_sharded_bitmap", rows=per_dev):
            bitmap, device_all_valid = fn(*args)
        if _trace.enabled():
            sp.annotate(placement=_placement([*args, bitmap]))
    bitmap = np.asarray(bitmap)[:n] & precheck
    # The ICI-reduced verdict covers device checks (padded rows verify
    # true by construction); AND with the host prechecks for the final
    # answer without another pass over the bitmap.
    return bitmap, bool(device_all_valid) and bool(precheck.all())
