"""Device-free perf smoke stages — the CI-budget tmperf path.

The full bench (bench.py) needs a device claim and most of a
15-minute budget; CI needs a perf signal it can afford every run.
These stages time the HOST planes (structural hash, mempool
admission) with micro workloads and small repeat counts through the
shared tmperf harness, appending canonical records to the perf
ledger. The one exception is the trailing `device-obs` stage, which
rates the tmdev residency sampler on the pinned CPU jax backend —
still no accelerator, but its records carry a live-backend
fingerprint (see _measure_device_obs). Two back-to-back runs of unchanged code must compare clean;
a real hot-path regression (the memoization breaking, the batched
admission path degrading to per-tx) lands far outside the noise
threshold even at this scale.

Noise honesty: within-run MAD cannot see whole-run CPU contention on
a shared CI box (a neighbor can slow an ENTIRE run's reps together),
so smoke gating on busy boxes should use a generous relative floor
(`tmperf gate --min-rel-delta 0.35`) — the compare defaults suit
quiet boxes and the device bench. docs/observability.md#tmperf.

Used by `scripts/tmperf.py record` and `python bench.py smoke`;
tier-1 tests drive it with tiny repeats (tests/test_perf.py).

Workload sizes are deliberately pinned in each record's `params`:
a 2k-tx smoke flood and bench.py's 50k flood are different workloads
and never gate against each other (perf/record.py record_key).
"""

from __future__ import annotations

import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

# the host planes under test never need a device; keep jax (if any
# stage pulls it in transitively) off the chip, which one process owns
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from tendermint_tpu.perf import (  # noqa: E402
    Samples,
    append_records,
    fingerprint,
    make_record,
    rate_samples,
)

SMOKE_STAGES = ("hash", "mempool", "proofs", "state", "device-obs")


def default_ledger() -> str:
    """BENCH_REPORT_DIR-aware (read at call time, like bench.py's
    report paths): a redirected bench run's smoke records must land in
    the same dir its report reads the ledger from."""
    out_dir = os.environ.get("BENCH_REPORT_DIR", os.path.join(_ROOT, ".bench_runs"))
    return os.path.join(out_dir, "ledger.jsonl")


def _measure_hash(repeats: int, min_time: float) -> list[tuple]:
    """(metric, unit, params, Samples) rows for the structural-hash
    plane: cold Header.hash (memo invalidated per call) and the
    1024-leaf merkle root on whichever backend is active."""
    import random

    from tendermint_tpu import native as N
    from tendermint_tpu.crypto import merkle as MK
    from tendermint_tpu.types.block import Header
    from tendermint_tpu.utils.tmtime import Time

    hd = Header(
        chain_id="perf-smoke", height=12345, time=Time(1700000000, 42),
        last_commit_hash=b"\x01" * 32, data_hash=b"\x02" * 32,
        validators_hash=b"\x03" * 32, next_validators_hash=b"\x04" * 32,
        consensus_hash=b"\x05" * 32, app_hash=b"\x06" * 32,
        last_results_hash=b"\x07" * 32, evidence_hash=b"\x08" * 32,
        proposer_address=b"\x09" * 20,
    )

    def header_cold():
        hd.height = 12345  # any field write invalidates the memo
        hd.hash()

    lib = N.load_prep()
    backend = "native" if lib is not None else "python"
    rng = random.Random(1234)
    items = [rng.randbytes(40) for _ in range(1024)]
    root = (lambda: N.merkle_root(items)) if backend == "native" else (
        lambda: MK._hash_from_byte_slices_py(items)
    )
    # warmup=2: the first measured call after import still pays
    # allocator/cache warmth — visible as a 20%-low first rep on a
    # busy CI box
    return [
        (
            "header_hash_per_sec", "headers/s", {"workload": "cold"},
            rate_samples(header_cold, repeats=repeats, warmup=2, min_time=min_time),
        ),
        (
            "merkle_root_per_sec", "roots/s",
            {"leaves": 1024, "backend": backend},
            rate_samples(root, repeats=repeats, warmup=2, min_time=min_time),
        ),
    ]


def _measure_mempool(repeats: int, min_time: float, flood: int) -> list[tuple]:
    """Batched admission (check_tx_batch: native batch hashing + one
    pipelined ABCI round + single-lock settle) of a `flood`-tx flood
    into a fresh pool per repetition — the PR-6 write path's smoke
    signal."""
    from tendermint_tpu.abci.client import LocalClient
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.mempool.mempool import TxMempool

    txs = [b"smoke-%d=%d" % (i, i) for i in range(flood)]

    def admit():
        pool = TxMempool(
            LocalClient(KVStoreApplication()),
            size=flood + flood // 4, cache_size=2 * flood + 1000,
        )
        out = pool.check_tx_batch(txs)
        ok = sum(1 for o in out if not isinstance(o, Exception) and o.is_ok)
        assert ok == flood, f"smoke flood admitted {ok}/{flood}"
        return flood  # units of work this call performed

    return [
        (
            "admitted_tx_per_sec", "tx/s",
            {"flood": flood, "transport": "local", "mode": "batched"},
            # min_time=0: each repetition is exactly one flood —
            # repeats carry the noise model, not inner-loop padding
            rate_samples(admit, repeats=repeats, warmup=1, min_time=0.0),
        ),
    ]


def _measure_proofs(repeats: int, min_time: float) -> list[tuple]:
    """Batched proof-serving smoke (tmproof, docs/observability.md
    #tmproof): ONE multiproof proving k=64 indices against a 4096-leaf
    tree — the build+prove path (native tm_merkle_multiproof when
    available) and the tree-cache-hot assembly path (zero hashing).
    Each fn returns k, so the samples read in proofs served per
    second, the unit the full bench's proofs stage also records."""
    import random

    from tendermint_tpu import native as N
    from tendermint_tpu.crypto import merkle as MK

    n, k = 4096, 64
    rng = random.Random(4242)
    items = [rng.randbytes(40) for _ in range(n)]
    idxs = sorted(rng.sample(range(n), k))
    lib = N.load_prep()
    backend = "native" if lib is not None else "python"
    tree = MK.TreeLevels.build(items)

    def build_and_prove():
        MK.multiproof_from_byte_slices(items, idxs)
        return k

    def hot_assemble():
        tree.multiproof(idxs)
        return k

    return [
        (
            "multiproof_proofs_per_sec", "proofs/s",
            {"leaves": n, "k": k, "mode": "build", "backend": backend},
            rate_samples(build_and_prove, repeats=repeats, warmup=2, min_time=min_time),
        ),
        (
            "multiproof_proofs_per_sec", "proofs/s",
            {"leaves": n, "k": k, "mode": "cache_hot"},
            rate_samples(hot_assemble, repeats=repeats, warmup=2, min_time=min_time),
        ),
    ]


def _measure_state(repeats: int, min_time: float) -> list[tuple]:
    """Incremental app-state smoke (tmstate, docs/state.md): one
    dirty-path commit (32 updated accounts in a 4096-account tree)
    per call, and the hot k=16 multiproof serve from the published
    view — the bank app-hash write path and the state_batch read
    path at CI budget. The micro workload is pinned in params, so
    it never gates against bench.py's 100k/1M tiers."""
    import random

    from tendermint_tpu.statetree import StateTree

    n, dirty_n, k = 4096, 32, 16
    rng = random.Random(77)
    tree = StateTree((b"acct:%08x" % i, b"v%d" % i) for i in range(n))
    ctr = [0]

    def commit():
        ctr[0] += 1
        picks = rng.sample(range(n), dirty_n)
        tree.apply({b"acct:%08x" % i: b"v%d-%d" % (i, ctr[0]) for i in picks})

    idxs = sorted(rng.sample(range(n), k))

    def serve():
        tree.latest().multiproof(idxs)
        return k

    return [
        (
            "commits_per_sec", "commits/s",
            {"accounts": n, "dirty": dirty_n, "mode": "path"},
            rate_samples(commit, repeats=repeats, warmup=2, min_time=min_time),
        ),
        (
            "proofs_per_sec", "proofs/s",
            {"accounts": n, "k": k},
            rate_samples(serve, repeats=repeats, warmup=2, min_time=min_time),
        ),
    ]


def _measure_device_obs(repeats: int, min_time: float) -> list[tuple]:
    """Residency-sampler steady-state cost through the observatory
    (tmdev, docs/observability.md#tmdev): install the jax.monitoring
    listener, park one live device buffer on the CPU backend, and rate
    the FlightRecorder sampler tick (jax.live_arrays walk + per-plane
    gauge updates). This is the ONE smoke stage that imports jax —
    it runs last (SMOKE_STAGES order) so the import cannot perturb the
    host-plane timings, and run_smoke stamps its records with a fresh
    live-backend fingerprint instead of the jax-free host one."""
    import jax.numpy as jnp

    from tendermint_tpu import devobs

    devobs.install()
    keep = jnp.zeros(1024, jnp.float32)  # a live buffer so the walk is non-trivial
    keep.block_until_ready()

    def tick():
        devobs.sample_residency()

    samples = rate_samples(tick, repeats=repeats, warmup=2, min_time=min_time)
    del keep
    # cadence_s pins the workload identity: the floor is "sampler cost
    # vs a 1s flight cadence", same key the full bench records
    return [("residency_samples_per_sec", "samples/s", {"cadence_s": 1.0}, samples)]


def run_smoke(
    stages=None,
    repeats: int = 5,
    min_time: float = 0.1,
    ledger_path: str | None = None,
    inject: dict | None = None,
    note: str | None = None,
    run_id: str | None = None,
    flood: int = 2000,
    log=None,
) -> tuple[str, list[dict]]:
    """Run the device-free smoke stages, append canonical records to
    the ledger, return (run_id, records).

    `inject` maps stage -> fractional slowdown (0.3 = 30% slower) and
    scales the measured samples down before recording — the
    documented hook tests and the acceptance demo use to prove the
    gate trips on a real delta without de-optimizing the code."""
    stages = list(stages) if stages else list(SMOKE_STAGES)
    unknown = set(stages) - set(SMOKE_STAGES)
    if unknown:
        raise ValueError(f"unknown smoke stages: {sorted(unknown)} (have {SMOKE_STAGES})")
    # ns suffix: two record calls in the same second (tests, scripted
    # demos) must be two runs, not one merged run group
    run_id = run_id or (
        f"smoke-{time.strftime('%Y%m%d-%H%M%S')}-{time.time_ns() % 1_000_000_000}"
    )
    ledger_path = ledger_path or default_ledger()
    fp = fingerprint(device="cpu")
    records = []
    for stage in stages:
        if stage == "hash":
            rows = _measure_hash(repeats, min_time)
        elif stage == "proofs":
            rows = _measure_proofs(repeats, min_time)
        elif stage == "state":
            rows = _measure_state(repeats, min_time)
        elif stage == "device-obs":
            rows = _measure_device_obs(repeats, min_time)
        else:
            rows = _measure_mempool(repeats, min_time, flood)
        # device-obs pulls jax in, so its records carry the live-backend
        # fingerprint (jax version + actual backend device) — computed
        # AFTER the measurement, never contaminating the jax-free fp the
        # host-plane floors were blessed under
        stage_fp = fingerprint(device="cpu") if stage == "device-obs" else fp
        slow_frac = float((inject or {}).get(stage, 0.0))
        for metric, unit, params, samples in rows:
            if slow_frac:
                samples = Samples(
                    [v * (1.0 - slow_frac) for v in samples.values],
                    warmup=samples.warmup,
                )
            rec = make_record(
                stage, metric, unit, samples,
                run_id=run_id, t=time.time(), params=params,
                provenance="smoke", fingerprint=stage_fp,
                note=note or (f"injected {slow_frac:.0%} slowdown" if slow_frac else None),
            )
            records.append(rec)
            if log is not None:
                log(f"{stage}/{metric} {params}: {samples.format()}"
                    + (f"  [injected -{slow_frac:.0%}]" if slow_frac else ""))
    append_records(ledger_path, records)
    return run_id, records
