"""Sharded batch verification over a device mesh.

The 10k-validator mega-commit path (BASELINE.md config 5): a batch's rows
are split along a 1-D mesh axis ("batch"), each chip runs the
per-signature kernel on its share, and the all-valid verdict is an
AND-reduce over ICI implemented as `psum(local_fail_count) == 0`. The
engine's "sharded" route (`ops/engine.py` `_dispatch_group`) launches
through `dispatch` and reads the bitmap back, in row order, through
`collect` on its collect thread.
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
from ..types.validator_set import MAX_VOTES_COUNT

AXIS = "batch"

# the batch-capable planes (secp256k1 has no batch equation — callers
# fall back to serial host verification, as in the reference): the
# plane's module, its cached split-ladder kernel and its table builder
_PLANES = {
    "ed25519": (V, V.verify_kernel_cached_split_impl, V.build_pk_tables_split),
    "sr25519": (VS, VS.verify_sr_kernel_cached_split_impl, VS.build_sr_tables_split),
}


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """A 1-D mesh over `devices` (default `jax.devices()`), cut to the
    first `n_devices` where given."""
    devices = list(jax.devices() if devices is None else devices)
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (AXIS,))


def chip_rows(n: int, chips: int) -> int:
    """Rows each chip runs for a batch of n: the share rounded up to a
    power of two up to 256, then to a multiple of 256. A bounded set of
    program shapes with at most ~2.5% padding at the 10k scale, where a
    power of two over the whole batch pads 10000 rows to 16384."""
    per = -(-n // chips)
    return V._pad_pow2(per, floor=8) if per <= 256 else -(-per // 256) * 256


def _plane(key_type: str):
    try:
        return _PLANES[key_type]
    except KeyError:
        raise ValueError(
            f"unsupported key_type {key_type!r} for sharded verification "
            f"(batch-capable: {sorted(_PLANES)})"
        ) from None


_FN_CACHE: dict[tuple, object] = {}
_CACHES: dict[tuple, V.PubkeyCache] = {}
_CACHES_LOCK = threading.Lock()


# The mesh cache's slots: every key of the largest set a commit can
# carry (MaxVotesCount) to a power of two, 256 MiB a chip. One size
# whatever the set: the slot count is part of the shape of every program
# that reads the tables, so a cache that grew would load each of them
# again.
CACHE_SLOTS = V._pad_pow2(MAX_VOTES_COUNT)


def mesh_cache(mesh: Mesh, key_type: str) -> V.PubkeyCache:
    """The mesh's pubkey cache for a plane: CACHE_SLOTS slots on every
    chip, replicated, so a launch reads its rows' tables on its own chip.
    A fill runs at CACHE_SLOTS rows whatever its misses: one build and
    one publish program for the cache's whole life."""
    with _CACHES_LOCK:
        cache = _CACHES.get((mesh, key_type))
        if cache is None:
            _, _, build = _plane(key_type)
            cache = _CACHES[(mesh, key_type)] = V.PubkeyCache(
                capacity=CACHE_SLOTS, build_fn=build, plane=f"{key_type}_sharded_pk",
                sharding=NamedSharding(mesh, P()), fill_rows=CACHE_SLOTS)
        return cache


def sharded_verify_fn(mesh: Mesh, kernel_impl):
    """Returns a jitted fn: (tables, oks) replicated on every chip, then
    (B,) slots and (B,32)x3 uint8 rows sharded over the mesh -> ((B,) bool
    bitmap sharded over the mesh, scalar all-valid replicated). B must
    divide evenly by the mesh size (pad on host). Each chip gathers its
    rows' tables from its own replica, so no collective moves table data
    and the verdict is the one psum AND-reduce. Memoized per (mesh,
    kernel) so jit's trace cache is effective across calls. kernel_impl
    is the plane's cached split-ladder kernel (`_PLANES`)."""
    key = (mesh, kernel_impl)
    fn = _FN_CACHE.get(key)
    if fn is None:
        spec = P(AXIS)

        def sharded_verify(tables, oks, slots, r_enc, s_bytes, k_bytes):
            ok = kernel_impl(tables, oks, slots, r_enc, s_bytes, k_bytes)
            fails = jnp.sum(jnp.where(ok, 0, 1))
            return ok, jax.lax.psum(fails, AXIS) == 0  # ICI AND-reduce

        fn = jax.jit(shard_map(sharded_verify, mesh=mesh,
                               in_specs=(P(), P(), spec, spec, spec, spec),
                               out_specs=(spec, P())))
        _FN_CACHE[key] = fn
    return fn


def dispatch(mesh: Mesh, pubkeys, msgs, sigs, key_type: str = "ed25519"):
    """Launch one batch over the mesh without blocking: the keys' slots
    in the mesh's pubkey cache (a miss's tables built on every chip),
    host prep, each chip's share padded to `chip_rows` and staged
    straight to that chip, the asynchronous call. Returns the handle
    `collect` takes, or None where the cache cannot take the batch's
    keys now (more than it holds, or every slot it could free pinned by
    other fills). Malformed pubkeys are keyed as zeros: they already
    fail precheck, which masks their rows at collect."""
    plane, kernel_impl, _ = _plane(key_type)
    n, chips = len(sigs), mesh.devices.size
    per_chip = chip_rows(n, chips)
    padded = per_chip * chips
    fid = _devobs.next_flow() if _devobs.enabled() else 0
    with _trace.span("ops.verify_dispatch", "ops", kernel="sharded", shards=chips,
                     rows=n, padded=padded, flow=fid) as sp:
        with _trace.span("ops.pk_cache_lookup", "ops", rows=n):
            keys = [pk if len(pk) == 32 else b"\x00" * 32 for pk in pubkeys]
            slots, tables, oks = mesh_cache(mesh, key_type).ensure_snapshot(keys)
        if slots is None:
            return None
        with _trace.span("ops.prep", "ops", rows=n):
            _, r_enc, s_bytes, k_bytes, precheck = plane.prepare_batch(pubkeys, msgs, sigs)
        with _trace.span("ops.launch", "ops", rows=n, padded=padded):
            arrays = [np.pad(x, ((0, padded - n), (0, 0))) for x in (r_enc, s_bytes, k_bytes)]
            # padded rows (s = k = 0) verify true against any valid key's
            # table: the batch's own last slot, not slot 0, which may
            # hold an unrelated invalid key and fail the psum verdict
            slots = np.pad(slots, (0, padded - n), mode="edge")
            shard = NamedSharding(mesh, P(AXIS))
            # host arrays go straight to their shards: jnp.asarray first
            # would commit the whole batch to one chip and copy from there
            nbytes = slots.nbytes + sum(x.nbytes for x in arrays)
            with _devobs.transfer_span("h2d", nbytes, flow=fid):
                args = [jax.device_put(x, shard) for x in (slots, *arrays)]
            with _devobs.attribution(fn=f"{key_type}_sharded", rows=per_chip, flow=fid):
                ok_dev, all_valid = sharded_verify_fn(mesh, kernel_impl)(tables, oks, *args)
        if _trace.enabled():
            sp.annotate(placement=_placement([tables, oks, *args, ok_dev]))
    m = _engine_metrics()
    m.kernel_launches.add(1, "sharded")
    m.sharded_launches.add(1, "bitmap")
    return ok_dev, all_valid, precheck, n, fid


def collect(handle) -> np.ndarray:
    """Block on a `dispatch` handle: the (n,) bitmap in row order, every
    chip's share in place, ANDed with the host prechecks."""
    ok_dev, _, precheck, n, fid = handle
    return V.read_back(ok_dev, n, fid)[:n] & precheck


def verify_batch_sharded(mesh: Mesh, pubkeys, msgs, sigs, key_type: str = "ed25519"):
    """`dispatch` then `collect`: (bitmap numpy (n,), all_valid bool),
    the ICI-reduced verdict ANDed with the host prechecks (padded rows
    verify true by construction)."""
    if not sigs:
        return np.zeros((0,), bool), False
    handle = dispatch(mesh, pubkeys, msgs, sigs, key_type)
    if handle is None:
        raise ValueError(f"the mesh's pubkey cache cannot take {len(set(pubkeys))} keys "
                         f"({CACHE_SLOTS} slots)")
    bitmap = collect(handle)
    return bitmap, bool(handle[1]) and bool(handle[2].all())


def _placement(arrays) -> list[list[int]]:
    """Device ids holding a shard (or replica) of each array, for the
    sharded spans: the trace shows where every input and the bitmap
    lived."""
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
    per_dev = chip_rows(n, n_dev)
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
