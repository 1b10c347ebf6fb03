"""Multi-host (DCN) entry points for the sharded verification plane.

The reference scales its communication backend across machines with a
custom TCP stack (SURVEY §5.8); the TPU-native analog is JAX's
multi-controller runtime: every host runs the same program, device
discovery spans the pod (`jax.devices()` is global after
`jax.distributed.initialize`), in-pod collectives ride ICI and
cross-pod collectives ride DCN — the `psum` AND-reduce in
`sharded_verify.py` needs no code change. What DOES change on
multi-host is data placement: a single controller can `device_put` a
full array, but in multi-controller each process holds only its local
shard and must assemble the global array with
`jax.make_array_from_process_local_data`. This module provides that
path; on a single controller it degenerates to the plain sharded call,
which is how it is tested in-container (the single-host mesh is
checked on a virtual CPU mesh by tests/test_batch_verify.py and on
four real chips by chip_smoke.py's sharded-4 phase).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import sharded_verify as sv


def initialize(coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None) -> None:
    """Join the multi-controller runtime (ref analog: the NCCL/MPI init
    the reference never needed because its backend is TCP-only; here one
    call wires every host's chips into one global device set). No-op
    when already initialized or when running single-controller."""
    if coordinator_address is None:
        return  # single-controller run: nothing to join
    # Detect an already-joined runtime WITHOUT touching jax.process_count()
    # or any other backend-initializing API: those would initialize XLA,
    # after which jax.distributed.initialize refuses to run ("must be
    # called before any JAX computations") and the join could never
    # succeed.
    try:
        from jax._src import distributed as _dist

        if getattr(getattr(_dist, "global_state", None), "client", None) is not None:
            return  # already distributed
    except ImportError:  # pragma: no cover - private API moved
        pass
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except RuntimeError as e:
        # Already-joined runtime that the private-API probe failed to
        # detect (e.g. jax._src.distributed moved): keep the documented
        # no-op contract instead of crashing startup. jax 0.9 raises
        # "distributed.initialize should only be called once"; older/
        # newer wordings covered by the other patterns.
        msg = str(e).lower()
        # Only the already-joined wordings are safe to swallow; "must be
        # called before any JAX computations" means the join is
        # IMPOSSIBLE (init-order bug) and must stay loud — swallowing it
        # would silently degrade a multihost deployment to single-host.
        if not any(pat in msg for pat in ("already initialized", "only be called once")):
            raise


def global_mesh() -> "jax.sharding.Mesh":
    """1-D mesh over every chip in the job, across all hosts. Axis
    layout note: jax.devices() orders devices so that intra-host (ICI)
    neighbors are adjacent; a 1-D batch axis therefore keeps most
    traffic of the AND-reduce on ICI with one DCN hop per host pair."""
    return sv.make_mesh()


def verify_batch_sharded_local(mesh, pubkeys, msgs, sigs, key_type: str = "ed25519"):
    """Multi-controller variant of verify_batch_sharded: each process
    passes only its LOCAL jobs; the global batch is the concatenation
    over processes (every process must call this collectively, with
    the same per-process count). Returns (local bitmap (n,), global
    all-valid bool).

    Single-controller (process_count == 1) this is exactly
    verify_batch_sharded."""
    if jax.process_count() == 1:
        return sv.verify_batch_sharded(mesh, pubkeys, msgs, sigs, key_type)
    from jax.experimental import multihost_utils

    plane, kernel_impl, _ = sv._plane(key_type)
    n = len(sigs)
    _, r, s, k, precheck = plane.prepare_batch(pubkeys, msgs, sigs)
    # the pubkey tables are replicated on every chip of the job, so every
    # process fills its mesh cache with the whole job's keys in one order
    # (the global batch's): a key then has one slot on every host
    keys = np.frombuffer(b"".join(pk if len(pk) == 32 else b"\x00" * 32 for pk in pubkeys),
                         np.uint8).reshape(n, 32)
    job_keys = multihost_utils.process_allgather(keys).reshape(-1, 32)
    slots, tables, oks = sv.mesh_cache(mesh, key_type).ensure_snapshot(
        [row.tobytes() for row in job_keys])
    if slots is None:
        raise ValueError(f"the mesh's pubkey cache cannot take the job's keys "
                         f"({sv.CACHE_SLOTS} slots)")
    slots = slots[jax.process_index() * n:(jax.process_index() + 1) * n]
    # pad the LOCAL shard to an equal per-process size (collective
    # contract: same n on every process keeps shapes static); a padded
    # row takes the batch's last slot, as in sv.dispatch
    n_local_dev = len(mesh.local_devices)
    pad = sv.chip_rows(n, n_local_dev) * n_local_dev - n
    if pad:
        r, s, k = (np.pad(x, ((0, pad), (0, 0))) for x in (r, s, k))
        slots = np.pad(slots, (0, pad), mode="edge")
    sharding = NamedSharding(mesh, P(sv.AXIS))
    args = [jax.make_array_from_process_local_data(sharding, x) for x in (slots, r, s, k)]
    fn = sv.sharded_verify_fn(mesh, kernel_impl)
    bitmap, device_all_valid = fn(tables, oks, *args)
    # addressable slice of the global bitmap = this process's rows;
    # addressable_shards iteration order is not contractually sorted by
    # global index, so order explicitly by each shard's global row start
    shards = sorted(
        bitmap.addressable_shards, key=lambda sh: sh.index[0].start or 0
    )
    local = np.concatenate([np.asarray(sh.data) for sh in shards])[:n]
    local &= precheck
    # global all-valid must also fold every process's HOST precheck
    # (one tiny DCN allgather; device checks are already psum-reduced)
    flags = multihost_utils.process_allgather(np.asarray([precheck.all()]))
    return local, bool(device_all_valid) and bool(flags.all())
