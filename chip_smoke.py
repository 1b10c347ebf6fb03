#!/usr/bin/env python3
"""Smoke run of commit verification on one TPU chip, end to end.

One process, the only one in the run that touches JAX. It claims the
chip, builds the native prep library, makes a seeded 1000-validator
chain, and drives the routes a node takes by default (no TM_TPU_*
variable set): a joiner block-syncs the chain from an in-process peer,
refuses a copy with one corrupted signature, a light client verifies a
150-validator header, a 4-validator commit stays on the host, and one
1024-row batch of valid, tampered and ZIP-215 edge rows agrees row for
row with the pure-Python oracle on every production kernel. With four
or more chips visible, 10 000 signatures are verified over a 4-device
mesh as well.

Every phase records its wall time, the programs compiled (by function,
with seconds and persistent-cache hits and misses), the cutovers it ran
under, the route each batch took and peak device memory. These are
smoke readings, not benchmark metrics. The summary goes to
<out>/chip_smoke_summary.json; the last line of stdout is one JSON
object, {"ok": true, "device": {...}}. Exit 0 only if every phase
passed; without a TPU the script exits 2 at once and prints no result.

    python3 chip_smoke.py [--seed N] [--out DIR]
    python3 chip_smoke.py --dry-run     # tiny sizes on XLA:CPU, control flow only
    python3 chip_smoke.py --phases fixture,sharded-4   # a partial run, marked as one
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import time
import traceback

RUN_LIMIT_S = 1150  # the driver allows 1200 s

# name -> (validators, blocks) and batch sizes: the deployments of
# BASELINE.json. The dry run keeps the shape and shrinks the scale.
REAL = dict(sync_vals=1000, sync_blocks=8, light_vals=150, local_vals=4,
            oracle_rows=1024, sharded_sigs=10_000)
DRY = dict(sync_vals=24, sync_blocks=5, light_vals=12, local_vals=4,
           oracle_rows=32, sharded_sigs=64)
# The dry run emulates the device plane on XLA:CPU: the device routes
# forced on, the compact fe_mul form (the slice form is pathological
# there), and cutovers scaled to its sizes.
DRY_ENV = {"TM_TPU_CRYPTO": "on", "TM_TPU_FE_MUL": "dot",
           "TM_TPU_BATCH_CUTOVER": "6", "TM_TPU_MSM_CUTOVER": "16"}


def log(msg: str) -> None:
    print(msg, flush=True)


def light_sigs(n: int) -> int:
    """Signatures verify_commit_light checks of n equal-power
    validators: it stops once more than 2/3 of the power has signed."""
    return n * 2 // 3 + 1


def differing_rows(got, want) -> list[int]:
    return [i for i, (a, b) in enumerate(zip(got, want)) if a != b][:16]


class Smoke:
    """The run's state: sizes, the fixtures phases share, and the
    per-phase readings."""

    def __init__(self, args):
        self.seed = args.seed
        self.dry_run = args.dry_run
        self.size = DRY if args.dry_run else REAL
        self.phases: list[dict] = []
        self.summary: dict = {"dry_run": args.dry_run, "seed": args.seed, "phases": self.phases}
        self.chain = None  # the blocksync-1k chain
        self.before: dict = {}  # engine counters when the current phase began
        self._compiles_seen = 0

    # ------------------------------------------------------------ readings

    def _counters(self) -> dict:
        from tendermint_tpu.metrics import engine_metrics

        m = engine_metrics()
        rows: dict = {}
        for _, labels, value in m.path_rows.samples():
            rows[labels["path"]] = rows.get(labels["path"], 0) + int(value)
        kernels = {labels["kernel"]: int(v) for _, labels, v in m.kernel_launches.samples()}
        return {"rows": rows, "kernels": kernels}

    def _compile_delta(self) -> tuple[dict, dict]:
        """Programs compiled (or loaded from the persistent cache) since
        the last call, by function and row bucket, and the cache events
        by function so far."""
        from tendermint_tpu import devobs

        st = devobs.status(tail=256)
        new = st["compiles"] - self._compiles_seen
        self._compiles_seen = st["compiles"]
        programs: dict = {}
        for ev in st["tail"][-min(new, len(st["tail"])):] if new else []:
            key = f"{ev['fn']}@{ev['rows']}" if ev["rows"] is not None else ev["fn"]
            p = programs.setdefault(key, {"n": 0, "seconds": 0.0})
            p["n"] += 1
            p["seconds"] = round(p["seconds"] + ev["dur_s"], 3)
        return programs, st["cache_events"]

    def cutovers(self) -> dict:
        from tendermint_tpu.crypto import ed25519 as ed

        return {"device": ed.DEVICE_BATCH_CUTOVER, "msm": ed.MSM_BATCH_CUTOVER}

    def expected_rows(self, batches: list[int]) -> dict:
        """Rows per engine path for batches of these sizes under the
        cutovers in force (crypto/ed25519.py, ops/engine.py)."""
        cut = self.cutovers()
        rows: dict = {}
        for n in batches:
            path = "host" if n < cut["device"] else "bitmap" if n < cut["msm"] else "two_phase_msm"
            rows[path] = rows.get(path, 0) + n
        return rows

    # -------------------------------------------------------------- phases

    def run_phase(self, name: str, fn) -> bool:
        """Run one phase. fn(rec) returns its detail dict, or (detail,
        batches) when its verification batches went through the engine:
        then the routes they took are checked against the cutovers."""
        import jax

        rec: dict = {"name": name, "ok": False, "cutovers": self.cutovers()}
        self.phases.append(rec)
        self.before = self._counters()
        t0 = time.perf_counter()
        try:
            detail = fn(rec)
            if isinstance(detail, tuple):
                detail, batches = detail
                self.check_routes(rec, batches)
            rec.update(detail)
            rec["ok"] = True
        except Exception:  # noqa: BLE001 - the phase failed: record it, fail the run
            rec["error"] = traceback.format_exc()
            print(rec["error"], file=sys.stderr, flush=True)
        rec["wall_s"] = round(time.perf_counter() - t0, 3)
        delta = self.delta()
        rec["rows_by_path"], rec["kernel_launches"] = delta["rows"], delta["kernels"]
        rec["programs"], self.summary["cache_events_by_fn"] = self._compile_delta()
        stats = jax.devices()[0].memory_stats() or {}
        rec["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        compiled = ", ".join(f"{k} {v['seconds']}s" for k, v in rec["programs"].items()
                             if v["seconds"] >= 0.5)
        log(f"[{'ok' if rec['ok'] else 'FAILED':>6}] {name}: {rec['wall_s']}s "
            f"rows={rec['rows_by_path']} launches={rec['kernel_launches']} "
            f"cutovers={rec['cutovers']} peak={rec['peak_bytes_in_use']}"
            + (f" compiled: {compiled}" if compiled else ""))
        return rec["ok"]

    def delta(self) -> dict:
        """Engine rows by path and kernel launches since the phase began."""
        now = self._counters()
        return {
            kind: {k: v - self.before[kind].get(k, 0) for k, v in now[kind].items()
                   if v != self.before[kind].get(k, 0)}
            for kind in ("rows", "kernels")
        }

    def check_routes(self, rec: dict, batches: list[int]) -> None:
        """Every batch of the phase took the route its size calls for:
        no batch at or above the device cutover was verified on the
        host, and nothing below it went to the device."""
        rec["batches"] = batches
        rec["expected_rows_by_path"] = want = self.expected_rows(batches)
        # the engine counts a batch after it has woken the caller
        deadline = time.monotonic() + 5.0
        while (got := self.delta()["rows"]) != want and time.monotonic() < deadline:
            time.sleep(0.05)
        if got != want:
            raise AssertionError(f"routes taken {got} differ from the routes expected {want} "
                                 f"for batches {batches} under cutovers {self.cutovers()}")

    # native plane ---------------------------------------------------------

    def phase_native(self, rec):
        from tendermint_tpu import native

        if native.native_disabled():
            raise AssertionError("TM_TPU_NATIVE disables the native plane")
        so = native._artifact_path()
        if os.path.exists(so):
            os.remove(so)  # a cache: this run proves cc builds it here
        lib = native.load_prep()
        if lib is None:
            raise AssertionError("prep library did not build or load (is cc installed?)")
        missing = [name for name in native._SIGNATURES if not hasattr(lib, name)]
        if missing:
            raise AssertionError(f"prep library lacks {missing}")
        return {"artifact": os.path.basename(so), "symbols": sorted(native._SIGNATURES)}

    # cutovers -------------------------------------------------------------

    def phase_autotune(self, rec):
        """The engine's one-shot probe, finished here so that every
        later phase is routed under the same cutovers."""
        from tendermint_tpu.metrics import engine_metrics
        from tendermint_tpu.ops import engine

        engine.maybe_autotune()
        m = engine_metrics()
        failures = sum(v for _, _, v in m.autotune_failures.samples())
        if failures:
            raise AssertionError("the autotune probe failed on this device (see the log)")
        autotuned = bool(sum(v for _, _, v in m.autotuned.samples()))
        if not self.dry_run and not autotuned:
            raise AssertionError("the autotune probe did not run on a TPU with no cutover pinned")
        rec["cutovers"] = self.cutovers()
        return {"autotuned": autotuned}

    # fixture --------------------------------------------------------------

    def phase_fixture(self, rec):
        from tendermint_tpu.blocksync import fixture

        n, blocks = self.size["sync_vals"], self.size["sync_blocks"]
        self.chain = fixture.build_chain(self.seed, n, blocks, chain_id="blocksync-1k")
        # applying height h verified the commit for h-1 in full
        return ({"validators": n, "blocks": blocks,
                 "last_block_hash": self.chain.block_hashes[-1].hex()},
                [n] * (blocks - 1))

    # blocksync-1k ---------------------------------------------------------

    def phase_blocksync(self, rec):
        from tendermint_tpu.blocksync import fixture

        chain, n = self.chain, self.size["sync_vals"]
        t0 = time.perf_counter()
        res = fixture.sync(chain, timeout=600.0)
        wall = time.perf_counter() - t0
        want = chain.height - 1  # block h is proven by block h+1
        if res.fatal is not None:
            raise AssertionError(f"the joiner halted: {res.fatal!r}")
        if res.peer_errors:
            raise AssertionError(f"the joiner blamed its peer: {[str(e.err) for e in res.peer_errors]}")
        if not res.caught_up or res.blocks_synced != want:
            raise AssertionError(f"synced {res.blocks_synced} of {want} blocks, "
                                 f"caught_up={res.caught_up}")
        for h in range(1, want + 1):
            if res.block_store.load_block(h).hash() != chain.block_hashes[h - 1]:
                raise AssertionError(f"block {h} differs from the source's")
        if res.state.app_hash != chain.app_hashes[want - 1]:
            raise AssertionError("the joiner's app hash differs from the source's")
        batches = [light_sigs(n)] * want + [n] * (want - 1)
        if "two_phase_msm" in self.expected_rows(batches) and self.delta()["kernels"].get("rlc", 0) <= 0:
            raise AssertionError("no MSM kernel launch on the two_phase_msm route")
        return ({"blocks_synced": res.blocks_synced, "sync_wall_s": round(wall, 3),
                 "app_hash": res.state.app_hash.hex()}, batches)

    # refusal --------------------------------------------------------------

    def phase_refusal(self, rec):
        from tendermint_tpu.blocksync import fixture
        from tendermint_tpu.crypto import ed25519_ref as ref
        from tendermint_tpu.crypto.batch import create_batch_verifier

        chain, n = self.chain, self.size["sync_vals"]
        commit_height = chain.height // 2
        bad_index = n // 8 + 1  # inside the +2/3 prefix that blocksync verifies
        served = fixture.corrupted_copy(chain, commit_height, bad_index)
        jobs = chain.commit_jobs(served.load_block(commit_height + 1).last_commit)
        # the whole commit as one batch: the device bitmap against the oracle
        bv = create_batch_verifier(jobs[0][0])
        for pk, msg, sig in jobs:
            bv.add(pk, msg, sig)
        ok, bitmap = bv.verify()
        oracle = [ref.verify(pk.bytes(), msg, sig, zip215=True) for pk, msg, sig in jobs]
        want = [i != bad_index for i in range(n)]
        if ok or bitmap != oracle or oracle != want:
            bad = [i for i, b in enumerate(bitmap) if not b]
            raise AssertionError(f"bitmap names {bad}, the oracle "
                                 f"{[i for i, b in enumerate(oracle) if not b]}, "
                                 f"corrupted index {bad_index}")
        batches = [n]
        if "host" not in self.expected_rows(batches):
            # the per-signature kernel names the row, on either device
            # route, behind tables built in this phase or an earlier one
            if self.delta()["kernels"].get("bitmap_cached", 0) <= 0:
                raise AssertionError("the per-signature kernel did not launch on the refusal")
            if self._counters()["kernels"].get("pk_table_build", 0) <= 0:
                raise AssertionError("the pubkey cache was never filled on the device")
        # the same commit served by a peer: the joiner refuses that height
        res = fixture.sync(chain, serve_from=served, timeout=600.0, until_peer_error=True)
        if res.fatal is not None or res.caught_up:
            raise AssertionError(f"expected a refusal: fatal={res.fatal!r} caught_up={res.caught_up}")
        if not res.peer_errors or f"wrong signature (#{bad_index})" not in str(res.peer_errors[0].err):
            raise AssertionError(f"expected 'wrong signature (#{bad_index})', got "
                                 f"{[str(e.err) for e in res.peer_errors]}")
        if not isinstance(res.peer_errors[0].err, ValueError):
            raise AssertionError("the refusal is not a verification verdict")
        if res.block_store.height() != commit_height - 1:
            raise AssertionError(f"joiner stored {res.block_store.height()} blocks, expected "
                                 f"{commit_height - 1}: height {commit_height} must be refused")
        stored = commit_height - 1
        # heights 1..commit_height verified light (the last one refused),
        # heights 2..stored validated in full while applying
        batches += [light_sigs(n)] * commit_height + [n] * (stored - 1)
        return ({"refused_height": commit_height, "bad_index": bad_index,
                 "peer_error": str(res.peer_errors[0].err)[:48]}, batches)

    # light-150 ------------------------------------------------------------

    def phase_light(self, rec):
        from tendermint_tpu.blocksync import fixture
        from tendermint_tpu.light import verifier
        from tendermint_tpu.types.light_block import SignedHeader
        from tendermint_tpu.utils.tmtime import Time

        n = self.size["light_vals"]
        chain = fixture.build_chain(self.seed + 1, n, 3, chain_id="light-150")

        def signed_header(h):
            return SignedHeader(chain.block_store.load_block(h).header,
                                chain.block_store.load_seen_commit(h))

        trusted, untrusted = signed_header(1), signed_header(3)
        vals = chain.state.validators
        now = Time.from_unix_ns(untrusted.header.time.unix_ns() + 10**9)
        verifier.verify_non_adjacent(
            chain.chain_id, trusted, vals, untrusted, vals,
            trusting_period_ns=14 * 86400 * 10**9, now=now, max_clock_drift_ns=10 * 10**9,
            trust_level=verifier.DEFAULT_TRUST_LEVEL,
        )
        trusting = n // 3 + 1  # stops once more than 1/3 of the trusted power has signed
        return ({"validators": n, "trusting_sigs": trusting, "light_sigs": light_sigs(n)},
                [n, n, trusting, light_sigs(n)])

    # localnet-4 -----------------------------------------------------------

    def phase_localnet(self, rec):
        from tendermint_tpu.blocksync import fixture
        from tendermint_tpu.types.validation import verify_commit

        n = self.size["local_vals"]
        chain = fixture.build_chain(self.seed + 2, n, 3, chain_id="localnet-4")
        block = chain.block_store.load_block(3)
        meta = chain.block_store.load_block_meta(2)
        verify_commit(chain.chain_id, chain.state.validators, meta.block_id, 2, block.last_commit)
        batches = [n, n, n]
        if set(self.expected_rows(batches)) != {"host"}:
            raise AssertionError(f"a {n}-validator commit is at or above the device cutover "
                                 f"{self.cutovers()['device']}")
        return {"validators": n}, batches

    # oracle ---------------------------------------------------------------

    def signed_rows(self, limit: int) -> list[tuple]:
        """Up to `limit` (pubkey bytes, msg, sig) rows the chain's
        validators already signed: its seen-commits, height by height."""
        rows: list = []
        for h in range(1, self.chain.height + 1):
            if len(rows) >= limit:
                break
            commit = self.chain.block_store.load_seen_commit(h)
            rows += [(pk.bytes(), msg, sig) for pk, msg, sig in self.chain.commit_jobs(commit)]
        return rows[:limit]

    def oracle_rows(self):
        """oracle_rows (pubkey, msg, sig) rows from the chain's own
        commits: mostly valid, with tampered messages, tampered R,
        s >= L, and the ZIP-215 edge (small-order key, identity R,
        s = 0) mixed in."""
        from tendermint_tpu.crypto import ed25519_ref as ref

        rows = [list(r) for r in self.signed_rows(self.size["oracle_rows"])]
        edge_sig = ref.compress(ref.IDENTITY) + b"\x00" * 32
        small = ref.small_order_points()
        for i, row in enumerate(rows):
            pk, msg, sig = row
            if i % 29 == 3:
                row[1] = msg + b"!"
            elif i % 31 == 5:
                row[2] = bytes([sig[0] ^ 4]) + sig[1:]
            elif i % 37 == 7:
                s = int.from_bytes(sig[32:], "little") + ref.L
                row[2] = sig[:32] + s.to_bytes(32, "little")
            elif i % 41 == 11:
                row[0], row[2] = small[(i // 41) % len(small)], edge_sig
        return [tuple(r) for r in rows]

    def phase_oracle(self, rec):
        from tendermint_tpu.crypto import ed25519_ref as ref
        from tendermint_tpu.crypto.batch import create_batch_verifier
        from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
        from tendermint_tpu.ops import msm, verify

        rows = self.oracle_rows()
        pks, msgs, sigs = (list(c) for c in zip(*rows))
        oracle = [ref.verify(pk, msg, sig, zip215=True) for pk, msg, sig in rows]
        if all(oracle) or not any(oracle):
            raise AssertionError("the oracle batch must mix accepted and rejected rows")
        # the served route: what a caller of the batch verifier gets
        bv = create_batch_verifier(Ed25519PubKey(pks[0]))
        for pk, msg, sig in rows:
            bv.add(Ed25519PubKey(pk), msg, sig)
        ok, bitmap = bv.verify()
        if ok or bitmap != oracle:
            raise AssertionError("batch verifier differs from the oracle at rows "
                                 f"{differing_rows(bitmap, oracle)}")
        # every production program once, directly, at the same rows
        for name, fn in (("verify_kernel", verify.verify_batch),
                         ("verify_kernel_cached_split", verify.verify_batch_cached)):
            got = [bool(b) for b in fn(pks, msgs, sigs)]
            if got != oracle:
                raise AssertionError(f"{name} differs from the oracle at rows "
                                     f"{differing_rows(got, oracle)}")
        good = [r for r, valid in zip(rows, oracle) if valid]
        good = (good * 2)[: len(rows)]  # all valid, same row count
        if not msm.verify_batch_rlc(*(list(c) for c in zip(*good))):
            raise AssertionError("msm_verify_kernel rejected an all-valid batch")
        one_bad = list(good)
        one_bad[len(good) // 2] = next(r for r, valid in zip(rows, oracle)
                                       if not valid and len(r[2]) == 64
                                       and int.from_bytes(r[2][32:], "little") < ref.L)
        if msm.verify_batch_rlc(*(list(c) for c in zip(*one_bad))):
            raise AssertionError("msm_verify_kernel accepted a batch with an invalid row")
        return ({"rows": len(rows), "accepted": sum(oracle), "rejected": len(rows) - sum(oracle)},
                [len(rows)])

    # four chips -----------------------------------------------------------

    def phase_sharded(self, rec):
        import jax

        from tendermint_tpu import trace
        from tendermint_tpu.parallel import sharded_verify as sv

        chain, total = self.chain, self.size["sharded_sigs"]
        rows = self.signed_rows(total)  # the commits already signed
        for i in range(total - len(rows)):  # and fresh ones up to the total
            key = chain.keys[i % len(chain.keys)]
            msg = b"sharded-%d-%d" % (self.seed, i)
            rows.append((key.pub_key().bytes(), msg, key.sign(msg)))
        pks, msgs, sigs = (list(c) for c in zip(*rows))
        if self.dry_run:  # the cache's 16384 slots are 1 GiB over four CPU devices
            sv.CACHE_SLOTS = 128
        mesh = sv.make_mesh(4)
        mesh_ids = sorted(d.id for d in mesh.devices.flat)
        bad = total // 2
        bad_sigs = list(sigs)
        bad_sigs[bad] = sigs[bad][:32] + bytes([sigs[bad][32] ^ 1]) + sigs[bad][33:]
        was_tracing = trace.enabled()
        trace.set_enabled(True)
        try:
            bitmap, all_valid = sv.verify_batch_sharded(mesh, pks, msgs, sigs)
            if not (all_valid and bitmap.all()):
                raise AssertionError("sharded bitmap plane rejected valid signatures")
            bitmap, all_valid = sv.verify_batch_sharded(mesh, pks, msgs, bad_sigs)
            if all_valid or [i for i, b in enumerate(bitmap) if not b] != [bad]:
                raise AssertionError("sharded bitmap plane did not localise the corrupted row")
            if sv.verify_batch_sharded_rlc(mesh, pks, msgs, sigs) is not True:
                raise AssertionError("sharded RLC rejected an all-valid batch")
            if sv.verify_batch_sharded_rlc(mesh, pks, msgs, bad_sigs) is not False:
                raise AssertionError("sharded RLC missed the corrupted row")
            events = trace.export()["traceEvents"]
        finally:
            trace.set_enabled(was_tracing)
        bitmap_spans = [ev for ev in events if ev.get("name") == "ops.verify_dispatch"
                        and ev["args"].get("kernel") == "sharded"]
        if len(bitmap_spans) != 2 or {ev["args"]["shards"] for ev in bitmap_spans} != {4}:
            raise AssertionError(f"sharded bitmap launches: {[ev['args'] for ev in bitmap_spans]}")
        rlc_spans = [ev for ev in events if ev.get("name") == "sharded.verify"]
        for ev in bitmap_spans + rlc_spans:
            for where in ev["args"]["placement"]:
                if where != mesh_ids:
                    raise AssertionError(f"{ev['name']}: an array lives on devices {where}, "
                                         f"the mesh is {mesh_ids}")
        return {"signatures": total, "mesh_devices": mesh_ids, "visible_devices": len(jax.devices()),
                "launches": len(bitmap_spans) + len(rlc_spans)}


def drain_engine(timeout: float = 30.0) -> None:
    """Every submitted job was awaited by its phase; make sure the
    engine's workers hold nothing before the process ends."""
    from tendermint_tpu.ops import engine

    eng = engine.get_engine()
    deadline = time.monotonic() + timeout
    while eng._pending or eng._inflight:
        if time.monotonic() > deadline:
            raise AssertionError("verification engine still holds work at exit")
        time.sleep(0.05)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--out", default=os.path.join(os.getcwd(), "chiprun_out"),
                    help="directory for chip_smoke_summary.json")
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny sizes on whatever backend jax has (XLA:CPU emulation of the "
                         "device routes); never a device reading")
    ap.add_argument("--phases", default="",
                    help="comma-separated phases to run instead of all of them (a chip-budget "
                         "saver; phases that read the chain need 'fixture'); the result is "
                         "marked partial")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(RUN_LIMIT_S, exit=True)  # a hang ends the run, loudly
    if args.dry_run:
        os.environ.update(DRY_ENV)

    # Claim: the only JAX process of the run, on what jax.devices() gives.
    import jax

    devices = jax.devices()
    dev = devices[0]
    if args.dry_run:
        log(f"DRY RUN platform={dev.platform}")
    elif dev.platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0] is {dev.platform}:{dev.device_kind}",
              file=sys.stderr)
        return 2
    import jaxlib

    from tendermint_tpu import devobs
    from tendermint_tpu.ops import enable_compile_cache

    from importlib import metadata

    try:
        libtpu_version = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # a CPU-only installation has none
        libtpu_version = None
    cache_dir = enable_compile_cache()
    devobs.install()
    smoke = Smoke(args)
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    smoke.summary.update(
        device=device,
        versions={"jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu_version},
        compile_cache_dir=cache_dir,
        compile_cache_entries_at_start=len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0,
        env={k: v for k, v in os.environ.items() if k.startswith(("TM_TPU_", "JAX_", "XLA_"))},
    )
    log(f"device: {device}  versions: {smoke.summary['versions']}")
    log(f"compile cache: {cache_dir} ({smoke.summary['compile_cache_entries_at_start']} entries)")
    from tendermint_tpu.ops import field

    smoke.summary["fe_mul"] = field._FE_MUL_MODE
    log(f"fe_mul form: {field._FE_MUL_MODE}")

    phases = [
        ("native", smoke.phase_native),
        ("autotune", smoke.phase_autotune),
        ("fixture", smoke.phase_fixture),
        ("blocksync-1k", smoke.phase_blocksync),
        ("refusal", smoke.phase_refusal),
        ("light-150", smoke.phase_light),
        ("localnet-4", smoke.phase_localnet),
        ("oracle", smoke.phase_oracle),
        ("sharded-4", smoke.phase_sharded),
    ]
    only = [name for name in args.phases.split(",") if name]
    unknown = set(only) - {name for name, _ in phases}
    if unknown:
        ap.error(f"unknown phases {sorted(unknown)}")
    t_run = time.perf_counter()
    ok = True
    for name, fn in phases:
        if only and name not in only:
            smoke.phases.append({"name": name, "ok": True, "skipped": "not selected"})
        elif name == "sharded-4" and len(devices) < 4:
            log(f"[  skip] sharded-4: skipped: {len(devices)} chip(s)")
            smoke.phases.append({"name": name, "ok": True, "skipped": f"{len(devices)} chip(s)"})
        elif not ok:
            smoke.phases.append({"name": name, "ok": False, "skipped": "an earlier phase failed"})
        else:
            ok = smoke.run_phase(name, fn)
    try:
        drain_engine()
    except AssertionError:
        traceback.print_exc()
        ok = False
    smoke.summary.update(
        ok=ok,
        partial=bool(only),
        wall_s=round(time.perf_counter() - t_run, 3),
        not_run=["sr25519", "secp256k1"],
        cutovers=smoke.cutovers(),
        claim=None,
    )
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "chip_smoke_summary.json"), "w") as f:
        json.dump(smoke.summary, f, indent=1, sort_keys=False)
    faulthandler.cancel_dump_traceback_later()
    log(f"sr25519, secp256k1: not run. total {smoke.summary['wall_s']}s; "
        f"summary in {os.path.join(args.out, 'chip_smoke_summary.json')}")
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    result = {"ok": True, "device": device}
    if args.dry_run:
        result["dry_run"] = True
    if only:
        result["partial"] = only
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
