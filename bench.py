"""North-star benchmark: ed25519 batch-verify sigs/sec on one chip.

Prints JSON lines {"metric", "value", "unit", "vs_baseline"}; the LAST
line is the result (the bench banks a small-batch number early and
overwrites it as larger batches succeed).

The device stages run on what jax.devices() gives, in this one process,
and the command exits non-zero when that is not a TPU: a rate from
XLA:CPU is never printed under a device unit. Stage deadlines are
in-process (SIGALRM -> exception), never SIGKILL, so the JAX client
shuts down cleanly and releases the chip. A device stage that fails or
overruns still only stops escalation (ROADMAP A1 turns the stages into
cells that fail the run).

The measured path is the full device pipeline (ops/verify.py):
decompression + [s]B - [k]A - R + cofactor clear for every signature,
pipelined (host prep + uint8 H2D of batch i+1 overlap compute of batch
i) — the production mode, where blocksync feeds the chip a stream of
per-height commit batches.

The CPU baseline is a native single-signature verifier loop: the
`cryptography` package's Ed25519 (OpenSSL) — the closest stand-in for
the reference's Go curve25519-voi serial path
(crypto/ed25519/ed25519.go Verify) — else the pure-Python oracle.
"""

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "scripts"))

from _bench_util import StageTimeout, stage_deadline  # noqa: E402

# 2048 deliberately omitted: it adds one more uncached compile for an
# interior point the 1024/8192 measurements already bracket.
BATCHES = (256, 1024, 8192)
BUDGET = float(os.environ.get("BENCH_BUDGET", "840"))
PIPELINE_ITERS = int(os.environ.get("BENCH_ITERS", "8"))
# Per-stage Chrome-trace artifacts (tendermint_tpu.trace): each stage's
# engine/dispatch spans land next to the numbers so BENCH rounds carry
# a timeline, not just totals. BENCH_TRACE=1 opts in; default is off so
# published rates exclude the tracer's hot-path overhead and stay
# comparable across rounds.
TRACE_DIR = os.environ.get("BENCH_TRACE_DIR", os.path.join(_ROOT, ".bench_traces"))
# Repetition count for the shared tmperf harness (perf/harness.py):
# every stage measures repeats independent timed blocks and reports
# median ± MAD instead of a one-shot rate.
BENCH_REPEATS = int(os.environ.get("BENCH_REPEATS", "3"))
_T0 = time.monotonic()


def _remaining():
    return BUDGET - (time.monotonic() - _T0)


def _log(msg):
    print(f"# [{time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


# tmperf perf ledger (tendermint_tpu/perf/, docs/observability.md#tmperf):
# every stage appends a canonical record — stage, metric, per-repetition
# samples, median + MAD, harness shape, environment fingerprint — to
# .bench_runs/ledger.jsonl (appended ACROSS runs: it is the trajectory
# `scripts/tmperf.py trend/compare/gate` reads, and the evidence the
# perf_regression gate holds PRs against). BENCH_PERF=off disables;
# failures never sink the banked numbers.
_PERF_RUN = f"bench-{time.strftime('%Y%m%d-%H%M%S')}-{os.getpid()}"
_DEVICE = "cpu"  # rewritten after the device claim (platform:device_kind)


def _perf_record(stage, metric, unit, samples, params=None, device=None, note=None):
    if os.environ.get("BENCH_PERF", "on") == "off":
        return
    try:
        from tendermint_tpu.perf import append_records, fingerprint, make_record

        out_dir = os.environ.get("BENCH_REPORT_DIR", os.path.join(_ROOT, ".bench_runs"))
        rec = make_record(
            stage, metric, unit, samples,
            run_id=_PERF_RUN, t=time.time(), params=params,
            provenance="bench", fingerprint=fingerprint(device=device or _DEVICE),
            note=note,
        )
        append_records(os.path.join(out_dir, "ledger.jsonl"), [rec])
    except Exception as e:  # noqa: BLE001 - telemetry must not sink the run
        _log(f"perf record failed ({stage}/{metric}): {type(e).__name__}: {e}")


def _measure(fn, min_time=0.25, repeats=None):
    """Median ± MAD rate of fn through the shared tmperf harness:
    warmed, `repeats` independent repetitions of at-least-
    min_time/repeats inner loops (perf/harness.py rate_samples).
    Returns a Samples — .median for ratios, .format() for logs with
    the noise bound attached."""
    from tendermint_tpu.perf import rate_samples

    repeats = repeats or BENCH_REPEATS
    return rate_samples(
        fn, repeats=repeats, warmup=1, min_time=max(min_time / repeats, 0.03)
    )


# Flight recorder over the whole bench run (metrics/flight.py): the
# process-global registry (engine/hash/mempool telemetry) is sampled
# every BENCH_FLIGHT_INTERVAL seconds into .bench_runs/timeseries.jsonl
# with a mark() per stage, so a bench regression arrives with a rate
# timeline (which stage, and when within it, the rate fell off) instead
# of one end-of-run total. BENCH_FLIGHT=off disables.
_FLIGHT = None


def _start_bench_flight() -> None:
    global _FLIGHT
    if os.environ.get("BENCH_FLIGHT", "on") == "off":
        return
    try:
        from tendermint_tpu.metrics import global_registry
        from tendermint_tpu.metrics.flight import FlightRecorder

        out_dir = os.environ.get("BENCH_REPORT_DIR", os.path.join(_ROOT, ".bench_runs"))
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, "timeseries.jsonl")
        try:
            os.remove(path)  # one timeline per bench run
        except OSError:
            pass
        _FLIGHT = FlightRecorder(
            [global_registry()], path,
            interval=float(os.environ.get("BENCH_FLIGHT_INTERVAL", "0.5")),
        )
        _FLIGHT.start()
        _log(f"flight recorder: {path} @ {_FLIGHT.interval}s")
    except Exception as e:  # noqa: BLE001 - telemetry must not sink the run
        _log(f"flight recorder failed to start: {type(e).__name__}: {e}")


def _flight_mark(stage: str) -> None:
    if _FLIGHT is not None:
        _FLIGHT.mark(stage)


def _install_devobs() -> None:
    """tmdev (tendermint_tpu/devobs): the device observatory rides
    the FULL bench run by default — compile counts, transfer bytes and
    live-buffer residency land in the bench report next to the rates,
    so a failed device run's postmortem starts from evidence instead
    of XLA error tails. The targeted device-free subcommands (mempool/
    proofs/state/smoke) do NOT install it: install() imports jax, and
    those paths must stay jax-free so their perf records keep the
    host-plane fingerprint their blessed floors were recorded under.
    BENCH_DEVOBS=off opts out; a jax without the monitoring API
    degrades to a warn-once no-op inside install()."""
    if os.environ.get("BENCH_DEVOBS", "on") == "off":
        return
    try:
        from tendermint_tpu import devobs

        if devobs.install() is not None:
            _log("devobs device observatory on -> tendermint_device_* metrics")
    except Exception as e:  # noqa: BLE001 - telemetry must not sink the run
        _log(f"devobs install failed: {type(e).__name__}: {e}")


def _write_bench_report() -> None:
    """Persist a tmlens-style fleet report for THIS bench process:
    dump the process-global registry (engine/hash/mempool telemetry the
    stages populated) into a one-node artifact dir and run the analyzer
    over it, so every bench run leaves the same fleet_report.json shape
    an e2e run does (with latency quantiles estimated from the live
    histograms). BENCH_REPORT=off disables; failures never sink the
    banked numbers."""
    if os.environ.get("BENCH_REPORT", "on") == "off":
        return
    try:
        from tendermint_tpu.lens.prom import parse_exposition
        from tendermint_tpu.metrics import global_registry

        out_dir = os.environ.get("BENCH_REPORT_DIR", os.path.join(_ROOT, ".bench_runs"))
        os.makedirs(out_dir, exist_ok=True)
        text = global_registry().gather()
        exp = parse_exposition(text)
        hists = {}
        for base in (
            "tendermint_engine_queue_wait_seconds",
            "tendermint_engine_launch_latency_seconds",
            "tendermint_engine_collect_latency_seconds",
            "tendermint_engine_coalesced_group_size",
            "tendermint_hash_merkle_build_seconds",
            "tendermint_mempool_admit_seconds",
            "tendermint_mempool_admit_batch_size",
        ):
            h = exp.histogram(base)
            if h is not None and h.count:
                hists[base] = {
                    "p50": h.quantile(0.5),
                    "p99": h.quantile(0.99),
                    "mean": h.mean(),
                    "count": h.count,
                }
        report = {
            "kind": "bench",
            "run": _PERF_RUN,
            "elapsed_s": round(time.monotonic() - _T0, 1),
            "series": len(exp.names()),
            "histograms": hists,
        }
        # tmperf: environment fingerprint (slow box vs slow build,
        # and which device_kind ran, as a report field)
        # plus the ledger digest + baseline comparisons for this dir
        try:
            from tendermint_tpu.perf import compare_run, fingerprint, summarize_for_report

            report["fingerprint"] = fingerprint(device=_DEVICE)
            lpath = os.path.join(out_dir, "ledger.jsonl")
            if os.path.exists(lpath):
                perf = summarize_for_report(lpath)
                perf["comparisons"] = compare_run(perf["records"], perf["baselines"])
                regs = [c for c in perf["comparisons"] if c["status"] == "regression"]
                perf["perf_regression"] = {
                    "ok": not regs,
                    "regressions": [c["reason"] for c in regs],
                }
                report["perf"] = perf
        except Exception as e:  # noqa: BLE001 - reporting must not sink the run
            report["perf_error"] = f"{type(e).__name__}: {e}"
        global _FLIGHT
        if _FLIGHT is not None:
            _FLIGHT.stop()
            from tendermint_tpu.lens.series import parse_timeseries, summarize_timeseries

            report["timeline"] = summarize_timeseries(parse_timeseries(_FLIGHT.path))
            report["timeseries"] = _FLIGHT.path
            _FLIGHT = None
        path = os.path.join(out_dir, "fleet_report.json")
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        with open(os.path.join(out_dir, "metrics.txt"), "w") as f:
            f.write(text)
        _log(f"bench lens report: {path} ({len(hists)} histogram families)")
    except Exception as e:  # noqa: BLE001 - reporting must not sink the run
        _log(f"bench lens report failed: {type(e).__name__}: {e}")


def _save_stage_trace(stage: str) -> None:
    """Flush the span ring into TRACE_DIR/<stage>.trace.json (Perfetto/
    chrome://tracing format) and clear it so the next stage's artifact
    holds only its own spans. No-op when tracing is disabled."""
    from tendermint_tpu import trace as T

    if not T.enabled():
        return
    try:
        os.makedirs(TRACE_DIR, exist_ok=True)
        path = os.path.join(TRACE_DIR, f"{stage}.trace.json")
        n = T.save(path)
        T.clear()
        _log(f"stage trace: {path} ({n} events)")
    except OSError as e:
        _log(f"stage trace save failed ({stage}): {e}")


def make_jobs(jobs, n):
    """Extend (pks, msgs, sigs) lists in place up to n entries."""
    from tendermint_tpu.crypto import ed25519_ref as ref

    pks, msgs, sigs = jobs
    sk = ref.gen_privkey(b"\x42" * 32)
    pk = sk[32:]
    for i in range(len(sigs), n):
        msg = b"bench-commit-vote-%d" % i
        pks.append(pk)
        msgs.append(msg)
        sigs.append(ref.sign(sk, msg))
    return jobs


def bench_cpu(jobs):
    pks, msgs, sigs = jobs
    # The baseline rate is per-signature; a 256-sample measures it as
    # well as the full set and keeps the budget for device work.
    n = min(256, len(sigs))
    try:
        from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey
        from cryptography.exceptions import InvalidSignature

        keys = [Ed25519PublicKey.from_public_bytes(pk) for pk in pks[:n]]
        t0 = time.perf_counter()
        for key, m, s in zip(keys, msgs[:n], sigs[:n]):
            try:
                key.verify(s, m)
            except InvalidSignature:
                raise AssertionError("cpu baseline rejected valid signature")
        dt = time.perf_counter() - t0
    except ImportError:
        from tendermint_tpu.crypto import ed25519_ref as ref

        n = min(32, n)
        t0 = time.perf_counter()
        for pk, m, s in zip(pks[:n], msgs[:n], sigs[:n]):
            assert ref.verify(pk, m, s, zip215=True)
        dt = time.perf_counter() - t0
    return n / dt


def bench_device(jobs, batch, cached: bool = False, repeats: int | None = None):
    from tendermint_tpu.ops import verify as V
    from tendermint_tpu.perf import Samples

    dispatch = V.verify_batch_cached_async if cached else V.verify_batch_async
    pks, msgs, sigs = jobs
    pks, msgs, sigs = pks[:batch], msgs[:batch], sigs[:batch]
    # Warm-up launch compiles the program (cached across runs); measure
    # steady-state pipelined throughput: every iteration pays full host
    # prep + uint8 H2D + kernel, iterations dispatched async so
    # transfers overlap compute. Sync once at end of each repetition
    # (one repetition = one PIPELINE_ITERS block → one rate sample;
    # the pipelining inside a block is the thing being measured, so
    # per-iteration timing would destroy it). The cached variant
    # routes through the HBM pubkey cache (hits after warm-up) — fair
    # vs the CPU baseline, which also pre-expands its keys outside the
    # timed loop (see bench_cpu).
    bitmap = V.collect(dispatch(pks, msgs, sigs))
    assert bool(bitmap.all()), "device rejected valid signatures (warm-up)"
    rates = []
    for _ in range(repeats or BENCH_REPEATS):
        t0 = time.perf_counter()
        inflight = [dispatch(pks, msgs, sigs) for _ in range(PIPELINE_ITERS)]
        bitmaps = [V.collect(d) for d in inflight]
        dt = (time.perf_counter() - t0) / PIPELINE_ITERS
        assert all(bool(b.all()) for b in bitmaps), "device rejected valid signatures"
        rates.append(batch / dt)
    return Samples(rates, warmup=1)


def emit(rate, cpu_rate, mad=None, n=None):
    doc = {
        "metric": "ed25519_batch_verify_throughput",
        "value": round(rate, 1),
        "unit": "sigs/sec/chip",
        "vs_baseline": round(rate / cpu_rate, 3),
    }
    if mad is not None:
        doc["mad"] = round(mad, 1)
        doc["n_samples"] = n
    print(json.dumps(doc), flush=True)


def make_fastsync_chain(n_vals: int = 1000, n_blocks: int = 2):
    """Blocksync-style replay material: n_blocks distinct 1000-validator
    commits (BASELINE config 3). Built with the shared commit factory
    from scripts/bench_baseline.py; ~2.5s of pure-Python signing per
    block."""
    from bench_baseline import make_commit

    out = []
    for h in range(1, n_blocks + 1):
        out.append(make_commit(n_vals, height=h))
    return out


def bench_coalesced(jobs, n_callers=4, per_call=256, iters=4):
    """Concurrent-caller throughput through the unified async
    verification engine (ops/engine.py): n_callers threads submit
    per_call-row batches simultaneously; the engine coalesces queued
    jobs into combined launches (device bitmap/MSM above the cutover,
    the threaded C host plane below it) and demuxes per-caller bitmaps.
    This is the multi-reactor production shape — blocksync
    verify-ahead, light-client bisection, and evidence verification in
    flight together. Returns aggregate sigs/s."""
    import threading

    from tendermint_tpu.ops import engine as E

    pks, msgs, sigs = jobs
    eng = E.get_engine()
    slices = [
        (pks[c * per_call:(c + 1) * per_call],
         msgs[c * per_call:(c + 1) * per_call],
         sigs[c * per_call:(c + 1) * per_call])
        for c in range(n_callers)
    ]
    # Warm-up: compile the BRACKET of coalesced shapes deterministically
    # with single submissions of 1x / 2x / n_callers x per_call rows —
    # how the timed threads' jobs group is a race against the dispatch
    # worker, so the timed region must only ever hit shapes compiled
    # here (intermediate group sizes pad to these pow2 programs).
    for mult in (1, 2, n_callers):
        lo_rows = ([], [], [])
        for sl in slices[:mult]:
            for part, rows in zip(lo_rows, sl):
                part.extend(rows)
        h = eng.submit("ed25519", *lo_rows)
        assert all(h.result()), "engine rejected valid signatures (warm-up)"

    errs = []

    def caller(c):
        try:
            for _ in range(iters):
                if not all(eng.submit("ed25519", *slices[c]).result()):
                    raise AssertionError("engine rejected valid signatures")
        except Exception as e:  # noqa: BLE001 - surface after join
            errs.append(e)

    threads = [threading.Thread(target=caller, args=(c,)) for c in range(n_callers)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    if errs:
        raise errs[0]
    return n_callers * per_call * iters / dt


def _rate(fn, min_time=0.25, min_iters=3):
    """Median calls/sec of fn — back-compat shim over the shared
    harness (`min_iters` is subsumed: every repetition loops until its
    time floor, so fast fns get plenty of iterations)."""
    del min_iters
    return _measure(fn, min_time=min_time).median


def bench_hash():
    """The host structural-hash plane (no device needed; runs before
    the claim): merkle root at 64/1024/16384 leaves through the native
    C builder, the iterative Python fallback, and the seed's recursive
    builder (the pre-plane baseline, kept inline here); ValidatorSet
    .hash @1000 validators cold vs cached; Header.hash cold vs cached.
    Emits header_hash_per_sec as a NON-final JSON line, once per
    backend (native plane enabled vs TM_TPU_NATIVE=0 fallback)."""
    import hashlib
    import random

    from tendermint_tpu import native as N
    from tendermint_tpu.crypto import merkle as MK
    from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
    from tendermint_tpu.types.block import Header
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet
    from tendermint_tpu.utils.tmtime import Time

    def seed_recursive_root(items):
        # the seed tree builder (recursive, list-slice copies) — the
        # baseline every plane rate is quoted against
        n = len(items)
        if n == 0:
            return hashlib.sha256(b"").digest()
        if n == 1:
            return MK.leaf_hash(items[0])
        k = MK._split_point(n)
        return MK.inner_hash(seed_recursive_root(items[:k]), seed_recursive_root(items[k:]))

    rng = random.Random(1234)
    lib = N.load_prep()
    native_ok = lib is not None
    backend_name = "native" if native_ok else "python"
    merkle_rates = {}
    for n in (64, 1024, 16384):
        items = [rng.randbytes(40) for _ in range(n)]
        s_seed = _measure(lambda: seed_recursive_root(items))
        s_py = _measure(lambda: MK._hash_from_byte_slices_py(items))
        s_nat = _measure(lambda: N.merkle_root(items)) if native_ok else None
        r_nat = s_nat.median if s_nat else 0.0
        merkle_rates[n] = (r_nat, s_py.median, s_seed.median)
        _perf_record(
            "hash", "merkle_root_per_sec", "roots/s",
            s_nat if native_ok else s_py,
            params={"leaves": n, "backend": backend_name},
        )
        _log(
            f"merkle root n={n}: native {s_nat.format() if s_nat else 'n/a'}, "
            f"python-iter {s_py.format()}, seed-recursive {s_seed.format()}"
            + (f" (native {r_nat / s_seed.median:.1f}x seed)" if native_ok else "")
        )

    from tendermint_tpu.crypto import encoding as _enc
    from tendermint_tpu.proto import messages as _pb

    vals = [
        Validator.new(Ed25519PubKey(bytes([i & 0xFF, i >> 8]) + bytes(30)), 10 + i)
        for i in range(1000)
    ]
    vs = ValidatorSet.new(vals)

    def valset_seed():
        # seed behavior: re-encode every SimpleValidator + recursive
        # merkle, every call — what each of the 4+ per-block hash()
        # sites used to pay
        seed_recursive_root([
            _pb.SimpleValidator(
                pub_key=_enc.pubkey_to_proto(v.pub_key), voting_power=v.voting_power
            ).encode()
            for v in vs.validators
        ])

    def valset_cold():
        vs._hash_cache = None  # set-level memo off; per-leaf encodes stay warm
        vs.hash()

    # seed recompute is pure Python by definition and the cached path
    # never touches merkle, so both are backend-independent; the COLD
    # rate (1000-leaf rebuild) is backend-dependent and is re-measured
    # inside the backend loop below
    s_vs_seed = _measure(valset_seed)
    s_vs_cached = _measure(vs.hash)
    r_vs_seed, r_vs_cached = s_vs_seed.median, s_vs_cached.median
    _perf_record(
        "hash", "valset_hash_per_sec", "hashes/s", s_vs_cached,
        params={"validators": 1000, "workload": "cached"},
    )
    _log(
        f"ValidatorSet.hash @1000: seed-recompute {s_vs_seed.format()}, "
        f"cached {s_vs_cached.format()} "
        f"(cached {r_vs_cached / r_vs_seed:,.0f}x seed)"
    )

    hd = Header(
        chain_id="bench", height=12345, time=Time(1700000000, 42),
        last_commit_hash=b"\x01" * 32, data_hash=b"\x02" * 32,
        validators_hash=b"\x03" * 32, next_validators_hash=b"\x04" * 32,
        consensus_hash=b"\x05" * 32, app_hash=b"\x06" * 32,
        last_results_hash=b"\x07" * 32, evidence_hash=b"\x08" * 32,
        proposer_address=b"\x09" * 20,
    )

    def header_cold():
        hd.height = 12345  # any field write invalidates the memo
        hd.hash()

    from tendermint_tpu.proto import messages as pb
    from tendermint_tpu.types.block import cdc_encode

    def header_seed():
        # seed behavior: recursive tree over the 14 encodes, no memo
        hd.height = 12345
        version_bz = pb.Consensus(block=hd.version_block, app=hd.version_app).encode()
        time_bz = pb.Timestamp(seconds=hd.time.seconds, nanos=hd.time.nanos).encode()
        seed_recursive_root([
            version_bz, cdc_encode(hd.chain_id), cdc_encode(hd.height), time_bz,
            hd.last_block_id.to_proto().encode(), cdc_encode(hd.last_commit_hash),
            cdc_encode(hd.data_hash), cdc_encode(hd.validators_hash),
            cdc_encode(hd.next_validators_hash), cdc_encode(hd.consensus_hash),
            cdc_encode(hd.app_hash), cdc_encode(hd.last_results_hash),
            cdc_encode(hd.evidence_hash), cdc_encode(hd.proposer_address),
        ])

    r_hd_seed = _measure(header_seed).median
    backends = ["native", "python"] if native_ok else ["python"]
    # NOTE on labels: `backend` is the PLANE CONFIG the iteration ran
    # under (native enabled vs TM_TPU_NATIVE=0). The 14-leaf header
    # tree sits below the native cutover by design (crypto/merkle.py
    # _NATIVE_MIN_LEAVES), so the header rates are backend-independent
    # — any delta between the two lines is timing noise. The
    # backend-DEPENDENT evidence in each line is valset1000_cold
    # (re-measured under the config) and merkle1024 (per-builder).
    for backend in backends:
        prior = os.environ.pop("TM_TPU_NATIVE", None)
        try:
            if backend == "python":
                os.environ["TM_TPU_NATIVE"] = "0"
            s_hd_cold = _measure(header_cold)
            s_hd_cached = _measure(hd.hash)
            s_vs_cold = _measure(valset_cold)
        finally:
            if prior is not None:
                os.environ["TM_TPU_NATIVE"] = prior
            else:
                os.environ.pop("TM_TPU_NATIVE", None)
        r_hd_cold, r_hd_cached = s_hd_cold.median, s_hd_cached.median
        r_vs_cold = s_vs_cold.median
        _perf_record(
            "hash", "header_hash_per_sec", "headers/s", s_hd_cold,
            params={"workload": "cold", "backend": backend},
        )
        _perf_record(
            "hash", "valset_hash_per_sec", "hashes/s", s_vs_cold,
            params={"validators": 1000, "workload": "cold", "backend": backend},
        )
        _log(
            f"Header.hash [{backend}]: cold {s_hd_cold.format()} (14 leaves "
            f"< native cutover: same code path both backends), cached "
            f"{s_hd_cached.format()}, seed {r_hd_seed:,.0f}/s; "
            f"ValidatorSet cold [{backend}]: {s_vs_cold.format()}"
        )
        r_nat, r_py, r_seed = merkle_rates[1024]
        print(
            json.dumps(
                {
                    "metric": "header_hash_per_sec",
                    "value": round(r_hd_cold, 1),
                    "unit": "headers/sec (cold recompute; 14-leaf tree is below the native cutover, so backend-independent)",
                    "vs_baseline": round(r_hd_cold / r_hd_seed, 3),
                    "mad": round(s_hd_cold.mad, 1),
                    "n_samples": len(s_hd_cold),
                    "backend": backend,
                    "cached_per_sec": round(r_hd_cached, 1),
                    "valset1000_seed_per_sec": round(r_vs_seed, 1),
                    "valset1000_cold_per_sec": round(r_vs_cold, 1),
                    "valset1000_cached_per_sec": round(r_vs_cached, 1),
                    "valset1000_cached_vs_seed": round(r_vs_cached / r_vs_seed, 1),
                    "merkle1024_per_sec": round(r_nat if backend == "native" else r_py, 1),
                    "merkle1024_vs_seed_recursive": round(
                        (r_nat if backend == "native" else r_py) / r_seed, 3
                    ),
                }
            ),
            flush=True,
        )


def bench_proofs(ks=(1, 64, 256), n_leaves=16384):
    """Device-free batched proof-serving stage (tmproof, ISSUE 15):
    proofs/s against an n_leaves-leaf tree for each k, across four
    serve paths — multiproof (ONE tm_merkle_multiproof call proving k
    indices, build + prove), tree-cache-hot multiproof (pure node
    assembly from held levels, zero hashing), per-proof (one full
    proofs_from_byte_slices per requested index: the pre-tmproof
    gateway behavior, which rebuilds the tree and all n aunt lists per
    request), and the seed's recursive proof builder at k=1 (the
    pre-plane baseline). Equivalence gate FIRST, like the mempool
    stage: multiproof accept/reject byte-identical to the k independent
    Proof.verify calls across a property sweep, native and Python node
    sets agreeing byte-for-byte.

    Emits one proofs_per_sec JSON line per k; vs_baseline is the ratio
    against the per-proof path at the same k (the ISSUE-15 acceptance
    number: >= 5x at k >= 64)."""
    import random

    from tendermint_tpu import native as N
    from tendermint_tpu.crypto import merkle as MK

    rng = random.Random(99)
    lib = N.load_prep()
    native_ok = lib is not None
    backend = "native" if native_ok else "python"

    # -- equivalence gate: multiproof == per-proof oracle, both backends
    for n in (1, 2, 3, 13, 100, 257, 1000):
        items = [rng.randbytes(rng.randrange(1, 120)) for _ in range(n)]
        root, proofs = MK.proofs_from_byte_slices(items)
        for k in sorted({1, max(1, n // 2), n}):
            idxs = sorted(rng.sample(range(n), k))
            mp_root, mp = MK.multiproof_from_byte_slices(items, idxs)
            assert mp_root == root, (n, k)
            leaves = [items[i] for i in idxs]
            oracle = all(proofs[i].verify(root, items[i]) for i in idxs)
            assert mp.verify(root, leaves) == oracle, (n, k)
            assert not mp.verify(root, [lf + b"x" for lf in leaves]), (n, k)
            levels = MK._levels_from_byte_slices_py(items)
            assert mp.nodes == MK._multiproof_nodes_from_levels(levels, idxs), (
                n, k, "native/python node-set divergence")
    _log("proofs equivalence gate: multiproof == per-proof oracle "
         f"(sweep, backend={backend})")

    items = [rng.randbytes(40) for _ in range(n_leaves)]
    tree = MK.TreeLevels.build(items)
    seed_rate = None
    headline = None
    for k in ks:
        idxs = sorted(rng.sample(range(n_leaves), k))

        def multi():
            MK.multiproof_from_byte_slices(items, idxs)
            return k

        def hot():
            tree.multiproof(idxs)
            return k

        def per_proof():
            # serve ONE index the pre-tmproof way: full rebuild, take
            # one aunt list (each request pays the whole tree)
            MK.proofs_from_byte_slices(items)
            return 1

        s_multi = _measure(multi)
        s_hot = _measure(hot)
        s_per = _measure(per_proof, min_time=0.5)
        ratio = s_multi.median / s_per.median
        _log(
            f"proofs n={n_leaves} k={k} [{backend}]: multiproof "
            f"{s_multi.format(0)} proofs/s, cache-hot {s_hot.format(0)}, "
            f"per-proof {s_per.format(0)} ({ratio:.1f}x per-proof)"
        )
        for mode, s in (("multiproof", s_multi), ("cache_hot", s_hot),
                        ("per_proof", s_per)):
            _perf_record(
                "proofs", "proofs_per_sec", "proofs/s", s,
                params={"leaves": n_leaves, "k": k, "mode": mode,
                        "backend": backend},
            )
        if k == 1 and seed_rate is None:
            # the seed's recursive proof builder (O(n log n) list-slice
            # copies), one full build per served proof — measured once
            def seed_proofs(sub=items):
                def rec(part):
                    m = len(part)
                    if m == 1:
                        return MK.leaf_hash(part[0]), [[]]
                    sp = MK._split_point(m)
                    lroot, launts = rec(part[:sp])
                    rroot, raunts = rec(part[sp:])
                    return MK.inner_hash(lroot, rroot), (
                        [a + [rroot] for a in launts]
                        + [a + [lroot] for a in raunts]
                    )
                rec(sub)
                return 1

            s_seed = _measure(seed_proofs, min_time=0.5, repeats=3)
            seed_rate = s_seed.median
            _perf_record(
                "proofs", "proofs_per_sec", "proofs/s", s_seed,
                params={"leaves": n_leaves, "k": 1, "mode": "seed"},
            )
            _log(f"proofs n={n_leaves} seed-recursive: {s_seed.format(2)} proofs/s")
        if k >= 64:
            assert ratio >= 5.0, (
                f"multiproof {s_multi.median:,.0f} proofs/s is under 5x the "
                f"per-proof path {s_per.median:,.0f} at k={k} (acceptance)"
            )
        doc = {
            "metric": "proofs_per_sec",
            "value": round(s_multi.median, 1),
            "unit": f"proofs/sec served ({n_leaves}-leaf tree, k={k} multiproof)",
            "vs_baseline": round(ratio, 3),
            "mad": round(s_multi.mad, 1),
            "n_samples": len(s_multi),
            "k": k,
            "backend": backend,
            "cache_hot_per_sec": round(s_hot.median, 1),
            "per_proof_per_sec": round(s_per.median, 1),
        }
        if seed_rate:
            doc["seed_per_sec"] = round(seed_rate, 2)
        print(json.dumps(doc), flush=True)
        headline = doc

    # tree-cache hit/miss accounting under a hot-height request mix
    from tendermint_tpu.crypto.merkle import TreeCache

    cache = TreeCache(capacity=4)
    heights = [1, 2, 3, 1, 2, 3, 1, 1, 4, 5, 6, 1]  # 1 stays hot
    for h in heights:
        cache.get_or_build(("txs", h), lambda: items[:1024])
    _log(f"tree cache mix: {cache.hits} hits / {cache.misses} misses / "
         f"{cache.evictions} evictions over {len(heights)} requests")
    return headline


def bench_state(counts=None, dirty=64, k_proof=16):
    """Device-free incremental app-state stage (tmstate, ISSUE 18):
    commits/s and proofs/s against the statetree at 1k/100k/1M
    accounts. Per account count, three commit modes — incremental
    (dirty-path-only rehash of a `dirty`-account write set, the bank's
    per-block cost after the rewire), full (hash_from_byte_slices over
    every leaf: the pre-tmstate `_compute_app_hash`, measured as the
    vs_baseline denominator), and structural (insert batches that
    reshape the tree; memo-copied subtrees bound the rehash) — plus
    k-account multiproof serves from the live view (the `state_batch`
    route's hot path). Equivalence gate FIRST, like the proofs stage:
    the incremental root must equal the full recompute across a
    randomized update/insert/delete sweep before anything is timed.

    Acceptance (ISSUE 18): incremental commits/s at 100k accounts
    >= 10x the full-recompute baseline. BENCH_STATE_COUNTS trims the
    account axis (preflight's state-dry runs '1000')."""
    import random

    from tendermint_tpu.crypto.merkle import hash_from_byte_slices
    from tendermint_tpu.statetree import StateTree, state_leaf

    if counts is None:
        raw = os.environ.get("BENCH_STATE_COUNTS", "1000,100000,1000000")
        counts = tuple(int(c) for c in raw.split(",") if c.strip())
    rng = random.Random(1234)
    val = b'{"balance":%d,"nonce":0}'

    # -- equivalence gate: incremental dirty-path root == full recompute
    model: dict = {}
    gate_tree = StateTree()
    for rounds in range(12):
        batch: dict = {}
        live = list(model)
        for _ in range(rng.randrange(0, 24)):
            op = rng.randrange(3)
            if op == 0 and live:
                batch[rng.choice(live)] = rng.randbytes(20)
            elif op == 1:
                batch[b"acct:%08x" % rng.randrange(1 << 24)] = rng.randbytes(20)
            elif live:
                batch[rng.choice(live)] = None
        for key, v in batch.items():
            if v is None:
                model.pop(key, None)
            else:
                model[key] = v
        got = gate_tree.apply(batch)
        want = hash_from_byte_slices(
            [state_leaf(key, v) for key, v in sorted(model.items())]
        )
        assert got == want, f"incremental/full root divergence at round {rounds}"
    _log("state equivalence gate: incremental dirty-path root == full recompute (sweep)")

    headline = None
    for n in counts:
        keys = [b"acct:%012x" % i for i in range(n)]
        items = [(key, val % i) for i, key in enumerate(keys)]
        t0 = time.monotonic()
        tree = StateTree(items)
        _log(f"state n={n}: tree built in {time.monotonic() - t0:.2f}s")
        ctr = [0]

        def inc_commit():
            # one block's worth of balance updates: dirty paths only
            ctr[0] += 1
            tree.apply({keys[rng.randrange(n)]: val % (n + ctr[0])
                        for _ in range(dirty)})
            return 1

        leaves = [state_leaf(key, v) for key, v in items]

        def full_commit():
            # the pre-tmstate app hash: every leaf re-hashed per block
            # (leaf list pre-built — the old path also re-serialized it,
            # so this baseline is conservative)
            hash_from_byte_slices(leaves, site="bank")
            return 1

        def struct_commit():
            # account creation reshapes the tree (two-pointer merge +
            # memo-copied unchanged subtrees)
            ctr[0] += 1
            base = ctr[0] * dirty
            tree.apply({b"acct:new%012x" % (base + j): b"1" for j in range(dirty)})
            return 1

        s_inc = _measure(inc_commit)
        s_full = _measure(full_commit, repeats=3)
        s_struct = _measure(struct_commit, repeats=3) if n <= 200_000 else None
        view = tree.latest()
        idxs = sorted(rng.sample(range(len(view)), min(k_proof, len(view))))

        def serve():
            view.multiproof(idxs)
            return len(idxs)

        s_proofs = _measure(serve)
        ratio = s_inc.median / s_full.median
        _log(
            f"state n={n} dirty={dirty}: incremental {s_inc.format(1)} commits/s, "
            f"full {s_full.format(2)} commits/s ({ratio:.1f}x), "
            + (f"structural {s_struct.format(2)} commits/s, " if s_struct else "")
            + f"proofs k={len(idxs)} {s_proofs.format(0)} proofs/s"
        )
        modes = [("incremental", s_inc), ("full", s_full)]
        if s_struct is not None:
            modes.append(("structural", s_struct))
        for mode, s in modes:
            _perf_record(
                "state", "commits_per_sec", "commits/s", s,
                params={"accounts": n, "dirty": dirty, "mode": mode},
            )
        _perf_record(
            "state", "proofs_per_sec", "proofs/s", s_proofs,
            params={"accounts": n, "k": len(idxs)},
        )
        if n == 100_000:
            assert ratio >= 10.0, (
                f"incremental commits/s {s_inc.median:,.1f} is under 10x the "
                f"full-recompute baseline {s_full.median:,.1f} at 100k accounts "
                "(ISSUE-18 acceptance)"
            )
        headline = {
            "metric": "state_commits_per_sec",
            "value": round(s_inc.median, 1),
            "unit": f"commits/sec ({n} accounts, {dirty} dirty)",
            "vs_baseline": round(ratio, 3),
            "mad": round(s_inc.mad, 1),
            "n_samples": len(s_inc),
            "accounts": n,
            "full_per_sec": round(s_full.median, 3),
            "proofs_per_sec": round(s_proofs.median, 1),
        }
        print(json.dumps(headline), flush=True)
    return headline


def bench_mempool(floods=(1000, 10000, 50000)):
    """Device-free mempool admission stage (runs under JAX_PLATFORMS=cpu
    like the hash stage): admitted
    tx/s at 1k/10k/50k-tx floods, batched (check_tx_batch: native batch
    hashing + one pipelined ABCI round + single-lock settle) vs the
    seed per-tx path (one blocking check_tx per tx), over BOTH
    transports — the in-process LocalClient and an EXTERNAL socket app
    (one subprocess, the production shape for non-builtin apps, where
    per-tx admission pays a full round trip per tx) — plus an
    engine-on/off signed flood through the pre-verification hook.

    Emits one admitted_tx_per_sec JSON line per (flood, mode);
    vs_baseline is the ratio against the per-tx path on the SAME
    transport/flood. The 50k socket ratio is the ISSUE-6 acceptance
    number. Also asserts batched outcomes == sequential outcomes on a
    mixed flood (dups, oversize, rejects) before timing anything."""
    import re
    import subprocess

    from tendermint_tpu import native as N
    from tendermint_tpu.abci.client import LocalClient
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.abci.socket import SocketClient
    from tendermint_tpu.mempool.mempool import TxMempool
    from tendermint_tpu.mempool.preverify import EngineTxPreVerifier, make_sig_tx

    N.sha256_batch([b"warm"])  # build/load the native hash plane once

    def mk_pool(client, flood, **kw):
        return TxMempool(
            client, size=flood + flood // 4, cache_size=2 * flood + 1000, **kw
        )

    def outcome_sig(o):
        if isinstance(o, Exception):
            return type(o).__name__
        return ("ok", o.code)

    # -- equivalence gate: batched == sequential on a mixed flood
    mixed = [b"m%d=%d" % (i, i) for i in range(64)]
    mixed[10] = mixed[3]          # intra-batch duplicate
    mixed.insert(20, b"x" * 2048)  # oversize (max_tx_bytes below)
    seq_pool = TxMempool(LocalClient(KVStoreApplication()), size=40, max_tx_bytes=1024)
    bat_pool = TxMempool(LocalClient(KVStoreApplication()), size=40, max_tx_bytes=1024)
    seq_out = []
    for tx in mixed:
        try:
            seq_out.append(seq_pool.check_tx(tx))
        except Exception as e:  # noqa: BLE001
            seq_out.append(e)
    bat_out = bat_pool.check_tx_batch(mixed)
    assert [outcome_sig(o) for o in seq_out] == [outcome_sig(o) for o in bat_out], \
        "batched admission diverged from sequential outcomes"
    assert seq_pool.reap_max_txs(-1) == bat_pool.reap_max_txs(-1)
    _log("mempool equivalence gate: batched == sequential (65-tx mixed flood)")

    # -- external socket app (the production external-app transport)
    proc = subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.abci.socket", "--addr", "tcp://127.0.0.1:0"],
        cwd=_ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    sock_cli = None
    try:
        line = proc.stdout.readline()
        m = re.search(r"tcp://[\d.]+:\d+", line)
        if m:
            sock_cli = SocketClient(m.group(0))
            sock_cli.start()
        else:
            _log(f"mempool stage: external app failed to start ({line!r}); socket modes skipped")

        last = {}
        for flood in floods:
            txs = [b"f%d-%d=%d" % (flood, i, i) for i in range(flood)]
            per_tx_sample = txs[: min(3000, flood)]
            transports = [("local", lambda: LocalClient(KVStoreApplication()))]
            if sock_cli is not None:
                transports.append(("socket", lambda: sock_cli))
            for tname, mk_client in transports:
                # per-tx baseline (seed path), measured on a sample —
                # the rate is per-tx constant and the full 50k loop
                # would burn a minute of budget per transport
                pool = mk_pool(mk_client(), flood)
                t0 = time.perf_counter()
                for tx in per_tx_sample:
                    pool.check_tx(tx)
                per_tx_rate = len(per_tx_sample) / (time.perf_counter() - t0)

                # batched admission through the shared harness: one
                # repetition = one whole flood into a FRESH pool, so
                # the median carries run-to-run noise, not intra-batch
                # variance (timer-hygiene: no more one-shot rates)
                from tendermint_tpu.perf import Samples

                reps = []
                for _ in range(BENCH_REPEATS):
                    pool = mk_pool(mk_client(), flood)
                    t0 = time.perf_counter()
                    out = pool.check_tx_batch(txs)
                    dt = time.perf_counter() - t0
                    ok = sum(1 for o in out if not isinstance(o, Exception) and o.is_ok)
                    assert ok == flood, f"flood admitted {ok}/{flood}"
                    reps.append(flood / dt)
                s_batched = Samples(reps)
                batched_rate = s_batched.median
                ratio = batched_rate / per_tx_rate
                _log(
                    f"mempool flood {flood} [{tname}]: per-tx {per_tx_rate:,.0f} tx/s, "
                    f"batched {s_batched.format(0)} tx/s ({ratio:.1f}x)"
                )
                _perf_record(
                    "mempool", "admitted_tx_per_sec", "tx/s", s_batched,
                    params={"flood": flood, "transport": tname, "mode": "batched"},
                )
                last[tname] = (flood, batched_rate, ratio)
                print(
                    json.dumps(
                        {
                            "metric": "admitted_tx_per_sec",
                            "value": round(batched_rate, 1),
                            "unit": f"tx/sec admitted ({tname} transport, {flood}-tx flood)",
                            "vs_baseline": round(ratio, 3),
                            "mad": round(s_batched.mad, 1),
                            "n_samples": len(s_batched),
                            "flood": flood,
                            "mode": f"batched_{tname}",
                            "per_tx_baseline": round(per_tx_rate, 1),
                        }
                    ),
                    flush=True,
                )
    finally:
        if sock_cli is not None:
            sock_cli.stop()
        proc.terminate()

    # -- engine-routed signed flood (pre-verification hook): batched
    # admission submits ONE coalesced engine batch; the per-tx path
    # verifies one signature per admission. 1024 txs keeps the
    # pure-Python signing prep (~2.5ms/sig) off the critical budget.
    n_signed = 1024
    signed = [make_sig_tx(b"\x42" * 32, b"s%d=%d" % (i, i)) for i in range(n_signed)]
    # warm the engine outside the timed region (first submit pays the
    # one-shot accelerator probe's jax import + worker thread startup)
    EngineTxPreVerifier()([signed[0]])
    from tendermint_tpu.perf import Samples

    reps = []
    for _ in range(BENCH_REPEATS):
        pool = mk_pool(
            LocalClient(KVStoreApplication()), n_signed,
            pre_verify=EngineTxPreVerifier(),
        )
        t0 = time.perf_counter()
        out = pool.check_tx_batch(signed)
        reps.append(n_signed / (time.perf_counter() - t0))
        assert all(not isinstance(o, Exception) and o.is_ok for o in out)
    s_signed = Samples(reps)
    pool = mk_pool(
        LocalClient(KVStoreApplication()), n_signed,
        pre_verify=EngineTxPreVerifier(),
    )
    sample = signed[:256]
    t0 = time.perf_counter()
    for tx in sample:
        pool.check_tx(tx)
    rates = {
        "batched_engine_on": s_signed.median,
        "per_tx_engine_on": len(sample) / (time.perf_counter() - t0),
    }
    _log(
        "mempool signed flood (1024 sig-txs): "
        + ", ".join(f"{k} {v:,.0f} tx/s" for k, v in sorted(rates.items()))
    )
    _perf_record(
        "mempool", "admitted_tx_per_sec", "tx/s", s_signed,
        params={"flood": n_signed, "mode": "engine_on", "signed": True},
    )
    print(
        json.dumps(
            {
                "metric": "admitted_tx_per_sec",
                "value": round(rates["batched_engine_on"], 1),
                "unit": "tx/sec admitted (signed flood, engine-coalesced pre-verify)",
                "vs_baseline": round(
                    rates["batched_engine_on"] / rates["per_tx_engine_on"], 3
                ),
                "mad": round(s_signed.mad, 1),
                "n_samples": len(s_signed),
                "flood": n_signed,
                "mode": "batched_engine_on",
                "per_tx_baseline": round(rates["per_tx_engine_on"], 1),
            }
        ),
        flush=True,
    )

    # -- flight-recorder overhead (acceptance: enabled <= 1% of this
    # stage; disabled is zero-cost by construction — no object, no
    # thread). One sample tick against the NOW fully-populated global
    # registry (every engine/hash/mempool family the floods above
    # touched), amortized over the default 1s e2e cadence: the steady-
    # state fraction of wall time the recorder costs a busy node is
    # per_sample / interval regardless of stage length.
    import tempfile

    from tendermint_tpu.metrics import global_registry
    from tendermint_tpu.metrics.flight import FlightRecorder

    tmp = tempfile.NamedTemporaryFile(suffix=".jsonl", delete=False)
    tmp.close()
    fr = FlightRecorder([global_registry()], tmp.name, interval=1.0)
    fr.sample_once()  # warm: file open + full anchor
    n_ticks = 200
    t0 = time.perf_counter()
    for _ in range(n_ticks):
        fr.sample_once()
    per_sample_s = (time.perf_counter() - t0) / n_ticks
    fr.stop()
    os.unlink(tmp.name)
    overhead_pct = 100.0 * per_sample_s / 1.0
    _log(
        f"flight recorder: {per_sample_s * 1e6:,.0f}us/sample vs 1s cadence "
        f"= {overhead_pct:.3f}% steady-state overhead"
    )
    assert overhead_pct <= 1.0, (
        f"flight recorder overhead {overhead_pct:.2f}% exceeds the 1% budget"
    )
    print(
        json.dumps(
            {
                "metric": "flight_sample_overhead_pct",
                "value": round(overhead_pct, 4),
                "unit": "% of wall time at the default 1s cadence",
                "per_sample_us": round(per_sample_s * 1e6, 1),
            }
        ),
        flush=True,
    )
    return last


def bench_device_obs():
    """tmdev device-observatory cost + correctness on the CPU backend
    (docs/observability.md#tmdev). Device-free by design — the
    observatory's own cost is backend-independent Python (listener
    dispatch, live_arrays walk), so the 1% budget is provable in CI.

    Two halves, mirroring the flight-recorder overhead stage:
      1. round-trip: a fresh jit probe under attribution must land an
         attributed compile event + h2d/d2h transfer bytes — proof the
         listener chain is live on this jax, not silently no-opped
         (the monitoring-API-drift failure mode).
      2. overhead: N residency samples against the live buffer set,
         amortized over the recorder's default 1s cadence; enabled
         must cost <= 1% of wall time. Disabled is zero-cost by
         construction (no listener registered, attribution and
         transfer spans short-circuit to plain yields).
    """
    from tendermint_tpu import devobs

    devobs.install()
    assert devobs.enabled(), "devobs install failed (jax.monitoring missing?)"

    import jax
    import jax.numpy as jnp

    @jax.jit
    def _probe(x):
        return (x * 3 + 1).sum()

    n = 64
    with devobs.attribution(fn="bench_probe", rows=n):
        with devobs.transfer_span("h2d", n * 4):
            xd = jnp.arange(n, dtype=jnp.int32)
        ok = _probe(xd)
        with devobs.transfer_span("d2h", 4):
            float(ok)
    st = devobs.status()
    assert st["enabled"] and st["compiles"] >= 1, f"no compiles observed: {st}"
    assert any(r.get("fn") == "bench_probe" for r in st["tail"]), (
        f"probe compile not attributed: {st['tail'][-4:]}"
    )
    assert st["transfer_bytes"]["h2d"] >= n * 4, f"h2d bytes unaccounted: {st}"

    devobs.sample_residency()  # warm: first live_arrays walk
    n_ticks = 200
    t0 = time.perf_counter()
    for _ in range(n_ticks):
        devobs.sample_residency()
    per_sample_s = (time.perf_counter() - t0) / n_ticks
    overhead_pct = 100.0 * per_sample_s / 1.0
    _log(
        f"device obs: {per_sample_s * 1e6:,.0f}us/residency sample vs 1s "
        f"cadence = {overhead_pct:.3f}% steady-state overhead "
        f"({st['compiles']} compiles attributed)"
    )
    assert overhead_pct <= 1.0, (
        f"device observatory overhead {overhead_pct:.2f}% exceeds the 1% budget"
    )
    s = _measure(devobs.sample_residency, min_time=0.25)
    _perf_record(
        "device-obs", "residency_samples_per_sec", "samples/s", s,
        params={"cadence_s": 1.0},
    )
    print(
        json.dumps(
            {
                "metric": "device_obs_sample_overhead_pct",
                "value": round(overhead_pct, 4),
                "unit": "% of wall time at the default 1s cadence",
                "per_sample_us": round(per_sample_s * 1e6, 1),
                "compiles_attributed": st["compiles"],
            }
        ),
        flush=True,
    )
    return overhead_pct


def bench_fastsync(chain, repeats: int | None = None):
    """Sequential verify_commit_light over the prebuilt chain — the
    per-block work of blocksync replay (reactor.go:582) on the device
    batch plane. Returns blocks/sec Samples (one full-chain pass per
    repetition). The ~667-sig batches pad to the same 1024-row program
    shapes the sigs/s stages already compiled."""
    from bench_baseline import CHAIN as BCHAIN
    from tendermint_tpu.perf import Samples
    from tendermint_tpu.types.validation import verify_commit_light

    vals0, c0 = chain[0]
    verify_commit_light(BCHAIN, vals0, c0.block_id, c0.height, c0)  # warm-up
    rates = []
    for _ in range(repeats or BENCH_REPEATS):
        t0 = time.perf_counter()
        for vals, commit in chain:
            verify_commit_light(BCHAIN, vals, commit.block_id, commit.height, commit)
        rates.append(len(chain) / (time.perf_counter() - t0))
    return Samples(rates, warmup=1)


def main():
    global _DEVICE
    if len(sys.argv) > 1 and sys.argv[1] == "device-obs":
        # targeted device-free run: `python bench.py device-obs`
        # (preflight's device-obs dry stage) — observatory round-trip +
        # residency-sampler overhead budget on the CPU backend
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        _start_bench_flight()
        _flight_mark("device-obs")
        bench_device_obs()
        _write_bench_report()
        sys.exit(0)
    if len(sys.argv) > 1 and sys.argv[1] == "mempool":
        # targeted device-free run: `python bench.py mempool`
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        _start_bench_flight()
        _flight_mark("mempool")
        bench_mempool()
        _write_bench_report()
        sys.exit(0)
    if len(sys.argv) > 1 and sys.argv[1] == "proofs":
        # targeted device-free run: `python bench.py proofs`
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        _start_bench_flight()
        _flight_mark("proofs")
        bench_proofs()
        _write_bench_report()
        sys.exit(0)
    if len(sys.argv) > 1 and sys.argv[1] == "state":
        # targeted device-free run: `python bench.py state [counts]` —
        # an argv counts list overrides BENCH_STATE_COUNTS (preflight's
        # state-dry stage runs `state 1000`)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if len(sys.argv) > 2:
            os.environ["BENCH_STATE_COUNTS"] = sys.argv[2]
        _start_bench_flight()
        _flight_mark("state")
        bench_state()
        _write_bench_report()
        sys.exit(0)
    if len(sys.argv) > 1 and sys.argv[1] == "smoke":
        # CI-budget device-free perf smoke: micro hash + mempool
        # stages through the tmperf harness into the perf ledger
        # (scripts/perf_smoke.py; `scripts/tmperf.py gate` judges it)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from perf_smoke import run_smoke

        run_id, records = run_smoke(log=_log)
        _write_bench_report()
        print(json.dumps({
            "metric": "perf_smoke_records",
            "value": len(records),
            "unit": f"ledger records (run {run_id})",
        }), flush=True)
        sys.exit(0)
    # The full run measures the device: claim it first, in this process,
    # and refuse to measure anything else under a device unit.
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _log(f"no TPU: jax.devices()[0] is {dev.platform}:{dev.device_kind}; "
             "the device stages do not run on it")
        sys.exit(2)
    _DEVICE = f"{dev.platform}:{dev.device_kind}"
    _log(f"claimed: {_DEVICE}")
    _install_devobs()
    from tendermint_tpu import trace as _tmtrace

    if os.environ.get("BENCH_TRACE", "").strip().lower() in ("1", "on", "true", "yes"):
        _tmtrace.set_enabled(True)
    if _tmtrace.enabled():  # TM_TPU_TRACE=1 alone also traces the run
        _log("tracing active: stage timelines in "
             f"{TRACE_DIR}; rates include tracer overhead")
    _start_bench_flight()
    jobs = ([], [], [])

    # Stage 1 (host): job generation (pure-Python signing, ~2.4ms/sig)
    # and the CPU baseline.
    make_jobs(jobs, BATCHES[-1])
    cpu_rate = bench_cpu(jobs)
    _log(f"cpu baseline (n={len(jobs[2])}): {cpu_rate:,.0f} sigs/s")
    fastsync_chain = None
    if os.environ.get("BENCH_FASTSYNC", "on") != "off":
        try:
            fastsync_chain = make_fastsync_chain()
            _log(f"fast-sync chain built: {len(fastsync_chain)} blocks x 1000 validators")
        except Exception as e:  # noqa: BLE001 - aux metric must not sink the run
            _log(f"fast-sync prep failed: {type(e).__name__}: {e}")

    # Stage 1.5 (no device): the host structural-hash plane. Cheap
    # (~30s) and device-independent; failures never sink the run.
    if os.environ.get("BENCH_HASH", "on") != "off":
        try:
            _flight_mark("hash")
            with stage_deadline(min(max(_remaining() - 60, 20), 120)):
                bench_hash()
            _save_stage_trace("hash")
        except StageTimeout:
            _log("hash stage hit deadline; continuing")
        except Exception as e:  # noqa: BLE001
            _log(f"hash stage failed: {type(e).__name__}: {e}")
    # Stage 1.55 (no device): the batched proof-serving plane
    # (tmproof) — device-free like the hash stage; failures never sink
    # the run.
    if os.environ.get("BENCH_PROOFS", "on") != "off":
        try:
            _flight_mark("proofs")
            with stage_deadline(min(max(_remaining() - 60, 20), 120)):
                bench_proofs()
            _save_stage_trace("proofs")
        except StageTimeout:
            _log("proofs stage hit deadline; continuing")
        except Exception as e:  # noqa: BLE001
            _log(f"proofs stage failed: {type(e).__name__}: {e}")
    # Stage 1.57 (no device): the incremental app-state plane
    # (tmstate) — device-free like the hash stage; failures never sink
    # the run.
    if os.environ.get("BENCH_STATE", "on") != "off":
        try:
            _flight_mark("state")
            with stage_deadline(min(max(_remaining() - 60, 20), 240)):
                bench_state()
            _save_stage_trace("state")
        except StageTimeout:
            _log("state stage hit deadline; continuing")
        except Exception as e:  # noqa: BLE001
            _log(f"state stage failed: {type(e).__name__}: {e}")
    # Stage 1.6 (no device): the coalesced tx-admission pipeline —
    # device-free like the hash stage; failures never sink the run.
    if os.environ.get("BENCH_MEMPOOL", "on") != "off":
        try:
            _flight_mark("mempool")
            with stage_deadline(min(max(_remaining() - 60, 20), 150)):
                bench_mempool()
            _save_stage_trace("mempool")
        except StageTimeout:
            _log("mempool stage hit deadline; continuing")
        except Exception as e:  # noqa: BLE001
            _log(f"mempool stage failed: {type(e).__name__}: {e}")

    # trace-time host constants (fixed-base comb tables, ~2s of Python
    # scalar mults) the kernels need — pay before the timed stages
    from tendermint_tpu.ops import curve as _curve

    _curve.fixed_base_table()
    _curve.base_table()

    # Stage 2.5: tmdev observatory round-trip + sampler overhead
    # budget; failures never sink the run.
    if os.environ.get("BENCH_DEVOBS", "on") != "off":
        try:
            _flight_mark("device-obs")
            with stage_deadline(min(max(_remaining() - 60, 20), 60)):
                bench_device_obs()
            _save_stage_trace("device-obs")
        except StageTimeout:
            _log("device-obs stage hit deadline; continuing")
        except Exception as e:  # noqa: BLE001
            _log(f"device-obs stage failed: {type(e).__name__}: {e}")

    # Stage 3: bank batches smallest-first; each success re-emits the
    # best rate so far. A stage timeout or error stops escalation but
    # keeps everything already banked.
    best = 0.0
    best_batch = 0
    for batch in BATCHES:
        rem = _remaining()
        if best and rem < 60:
            _log(f"budget exhausted ({rem:.0f}s left); stopping at banked result")
            break
        try:
            _flight_mark(f"device_b{batch}")
            with stage_deadline(rem - 15 if best else rem):
                s = bench_device(jobs, batch)
        except StageTimeout:
            _log(f"batch {batch} hit stage deadline; stopping escalation")
            break
        except Exception as e:  # noqa: BLE001 - bank what we have
            _log(f"batch {batch} failed: {type(e).__name__}: {e}")
            break
        _log(f"batch {batch}: {s.format(0)} sigs/s pipelined")
        _perf_record(
            "engine", "ed25519_batch_verify_throughput", "sigs/sec/chip", s,
            params={"batch": batch, "cached": False},
        )
        _save_stage_trace(f"device_b{batch}")
        best_batch = batch
        if s.median > best:
            best = s.median
            emit(best, cpu_rate, mad=s.mad, n=len(s))

    # Stage 4: the HBM-pubkey-cache path at the largest banked batch —
    # production steady state (validator sets repeat every height).
    # Only ever improves the banked line; failures change nothing.
    if best and _remaining() > 75:
        try:
            _flight_mark("cached")
            with stage_deadline(min(_remaining() - 15, 240)):
                s = bench_device(jobs, best_batch, cached=True)
            _log(f"batch {best_batch} cached: {s.format(0)} sigs/s pipelined")
            _perf_record(
                "engine", "ed25519_batch_verify_throughput", "sigs/sec/chip", s,
                params={"batch": best_batch, "cached": True},
            )
            _save_stage_trace("cached")
            if s.median > best:
                best = s.median
                emit(best, cpu_rate, mad=s.mad, n=len(s))
        except StageTimeout:
            _log("cached stage hit deadline; keeping uncached result")
        except Exception as e:  # noqa: BLE001
            _log(f"cached stage failed: {type(e).__name__}: {e}")
    # Stage 5: the RLC/MSM all-valid fast path — production phase 1 for
    # batches >= the MSM cutover (crypto/ed25519.py), i.e. the rate the
    # framework actually verifies honest commits at. Only ever improves
    # the banked line.
    if best and _remaining() > 75:
        from tendermint_tpu.ops import msm as M

        pks, msgs, sigs = (x[:best_batch] for x in jobs)
        try:
            from tendermint_tpu.perf import Samples

            _flight_mark("msm")
            msm_rates = []
            with stage_deadline(min(_remaining() - 15, 300)):
                h = M.verify_batch_rlc_async(pks, msgs, sigs)
                assert M.collect_rlc(h), "MSM rejected valid batch (warm-up)"
                for _ in range(BENCH_REPEATS):
                    t0 = time.perf_counter()
                    inflight = [
                        M.verify_batch_rlc_async(pks, msgs, sigs) for _ in range(PIPELINE_ITERS)
                    ]
                    oks = [M.collect_rlc(x) for x in inflight]
                    dt = (time.perf_counter() - t0) / PIPELINE_ITERS
                    assert all(oks), "MSM rejected valid batch"
                    msm_rates.append(best_batch / dt)
            s = Samples(msm_rates, warmup=1)
            _log(f"batch {best_batch} msm: {s.format(0)} sigs/s pipelined")
            _perf_record(
                "msm", "ed25519_msm_throughput", "sigs/sec/chip", s,
                params={"batch": best_batch, "cached": False},
            )
            _save_stage_trace("msm")
            if s.median > best:
                best = s.median
                emit(best, cpu_rate, mad=s.mad, n=len(s))
        except StageTimeout:
            _log("msm stage hit deadline; keeping prior result")
        except Exception as e:  # noqa: BLE001
            _log(f"msm stage failed: {type(e).__name__}: {e}")

    # Stage 6: the second north-star metric — fast-sync blocks/sec at
    # 1000 validators (BASELINE config 3). Emitted as a NON-final line
    # (the driver banks the LAST line, which stays the headline sigs/s
    # metric); vs_baseline is relative to serial-CPU block verification
    # of the same ~667-sig commits.
    if best and fastsync_chain is not None and _remaining() > 60:
        try:
            _flight_mark("fastsync")
            with stage_deadline(min(_remaining() - 15, 240)):
                s = bench_fastsync(fastsync_chain)
            cpu_blocks = cpu_rate / 667.0
            _log(f"fast-sync: {s.format()} blocks/s @1000 vals")
            _perf_record(
                "fastsync", "fast_sync_blocks_per_sec",
                "blocks/sec/chip @1000 validators", s,
                params={"validators": 1000},
            )
            _save_stage_trace("fastsync")
            print(
                json.dumps(
                    {
                        "metric": "fast_sync_blocks_per_sec",
                        "value": round(s.median, 2),
                        "unit": "blocks/sec/chip @1000 validators",
                        "vs_baseline": round(s.median / cpu_blocks, 3),
                        "mad": round(s.mad, 2),
                        "n_samples": len(s),
                    }
                ),
                flush=True,
            )
        except StageTimeout:
            _log("fast-sync stage hit deadline")
        except Exception as e:  # noqa: BLE001
            _log(f"fast-sync stage failed: {type(e).__name__}: {e}")

    # Stage 7: coalesced multi-caller throughput through the unified
    # async verification engine — the first engine-plane metric:
    # coalesced device launches. Non-final line.
    if _remaining() > 45:
        try:
            from tendermint_tpu.perf import Samples

            _flight_mark("coalesced")
            with stage_deadline(min(_remaining() - 15, 240)):
                # each bench_coalesced call warms its own shape
                # bracket, so one call = one clean repetition
                s = Samples(
                    [bench_coalesced(jobs) for _ in range(BENCH_REPEATS)],
                    warmup=0,
                )
            _log(f"coalesced 4-caller engine throughput: {s.format(0)} sigs/s")
            _perf_record(
                "coalesced", "coalesced_verify_throughput", "sigs/sec", s,
                params={"callers": 4, "per_call": 256},
            )
            _save_stage_trace("coalesced")
            print(
                json.dumps(
                    {
                        "metric": "coalesced_verify_throughput",
                        "value": round(s.median, 1),
                        "unit": "sigs/sec (4 concurrent callers x 256)",
                        "vs_baseline": round(s.median / cpu_rate, 3),
                        "mad": round(s.mad, 1),
                        "n_samples": len(s),
                    }
                ),
                flush=True,
            )
        except StageTimeout:
            _log("coalesced stage hit deadline")
        except Exception as e:  # noqa: BLE001
            _log(f"coalesced stage failed: {type(e).__name__}: {e}")

    _write_bench_report()
    if best:
        # Re-emit so the final stdout line is the best banked number
        # regardless of any later stderr interleaving in the driver's
        # captured tail.
        emit(best, cpu_rate)
    sys.exit(0 if best else 1)


if __name__ == "__main__":
    main()
