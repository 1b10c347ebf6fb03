"""Unified async verification engine (the process-wide dispatch plane).

Every batch-capable caller — blocksync verify-ahead, light-client
bisection, evidence verification, consensus commit checks — used to
dispatch its own device launch (or fall back to a serial host loop)
independently. Committee-signature verification amortizes best over
large combined batches (EdDSA/BLS committee study, arxiv 2302.00418),
and hardware verification engines win by pipelining prep/transfer/
compute stages rather than by faster single ops (FPGA ECDSA engine,
arxiv 2112.02229). This module is that pipeline:

  coalescing   — concurrent callers' jobs merge into ONE launch with
                 per-caller result demux: three 67-sig commits become a
                 single 256-row launch instead of three sub-cutover
                 host fallbacks.
  double-buffer— a dispatch worker runs host prep (native prep.c where
                 available) + the async kernel launch for batch i+1
                 while batch i's kernel still runs; a collect worker
                 blocks on results and demuxes. JAX queues launches, so
                 prep genuinely overlaps device compute.
  host plane   — below the device cutover (or with no accelerator) the
                 coalesced batch runs through libcrypto's EVP loop in C
                 (native/prep.c tm_host_verify): one GIL-free call,
                 threaded across cores, with the ZIP-215 oracle
                 re-checking only rows OpenSSL rejects — byte-identical
                 acceptance to the serial path.
  autotune     — DEVICE_BATCH_CUTOVER comes from a one-shot microprobe
                 of real launch latency vs host verify rate when an
                 accelerator is present, finished before the first
                 batch is routed. MSM_BATCH_CUTOVER, the choice between
                 the two device programs, is not probed (one program
                 load costs 10-30 s): it is looked up by device kind in
                 MSM_CUTOVER_ROWS, the measured crossover of the two
                 programs' launch prices (env still wins for both).

This is the only router: both BatchVerifiers and the mempool's
pre-verifier submit here, and VerifyEngine._dispatch_group alone chooses
host / split across the chips / per-signature / two-phase MSM for a
batch. The module imports no jax; a node pinned to the host
(TM_TPU_CRYPTO=off) runs the host plane and never loads it.
"""

from __future__ import annotations

import functools
import os
import threading
import time as _time


from .. import trace as _trace
from ..metrics import engine_metrics as _engine_metrics

# Rows per coalesced launch. Jobs beyond this form the next batch (the
# double buffer absorbs them); bounds both padding waste and the jit
# shape zoo.
MAX_COALESCE_ROWS = 8192

# A chip's share of a group from which the group is split across the
# process's chips: the per-signature program is row-bound from 512 rows
# (scripts/route_prices.py), so a smaller share would pay a launch a
# chip for rows one chip runs in about the same time.
SHARD_MIN_ROWS = 512


def splits(chips: int, rows: int) -> bool:
    """The sharded route's rule: a device-routed group of `rows` goes
    over `chips` chips when every chip's share is at least
    SHARD_MIN_ROWS."""
    return chips > 1 and rows >= chips * SHARD_MIN_ROWS


def _local_tpu_mesh():
    """A mesh over the process's own TPU chips where it has more than
    one, else None: the chips a group can be split over."""
    import jax

    devices = jax.local_devices()
    if len(devices) < 2 or devices[0].platform != "tpu":
        return None
    from ..parallel.sharded_verify import make_mesh

    return make_mesh(devices=devices)


# ------------------------------------------------------------------ autotune


_AUTOTUNE = {"done": False}
_AUTOTUNE_LOCK = threading.Lock()


def _autotune_enabled() -> bool:
    return os.environ.get("TM_TPU_AUTOTUNE", "auto").strip().lower() not in (
        "off", "0", "false", "no",
    )


def maybe_autotune() -> None:
    """One-shot cutover microprobe, finished before the first batch is
    routed. When the device plane is in use on a real accelerator and
    the env didn't pin TM_TPU_BATCH_CUTOVER / TM_TPU_MSM_CUTOVER,
    measure (a) the host per-signature verify time and (b) the warm
    end-to-end latency of a tiny device launch, and set the device
    cutover to the batch size where the device launch actually pays for
    itself; the MSM cutover is read from MSM_CUTOVER_ROWS for the device
    kind in use, with no launch of its own.
    Both prices, and that of the host route as the engine runs it (one
    coalesced batch through the C loop; it decides nothing yet), are
    published as gauges (engine_autotune_*_seconds).
    The probe runs under the lock on the first caller to arrive — the
    engine's dispatch worker, or a set-up step that asks for it early —
    and the other waits for it, so no batch is routed while the
    cutovers change; callers of VerifyEngine.submit never wait. The
    tiny launch may compile on a fresh cache; the first routed batch
    pays that once, beside its own program's compile. A failed probe is
    logged and counted (engine_autotune_failures_total) and leaves the
    defaults in force. No accelerator (or TM_TPU_AUTOTUNE=off) leaves
    the defaults untouched, so CPU test runs stay deterministic."""
    if _AUTOTUNE["done"]:
        return
    with _AUTOTUNE_LOCK:
        if _AUTOTUNE["done"]:
            return
        try:
            dev_pinned = "TM_TPU_BATCH_CUTOVER" in os.environ
            msm_pinned = "TM_TPU_MSM_CUTOVER" in os.environ
            if _autotune_enabled() and not (dev_pinned and msm_pinned):
                _autotune_probe(dev_pinned, msm_pinned)
        except Exception as exc:  # noqa: BLE001 - report, keep the defaults
            import traceback

            from ..utils.log import new_logger

            _engine_metrics().autotune_failures.add(1)
            new_logger("engine").error(
                "autotune probe failed; default cutovers stay in force",
                err=f"{type(exc).__name__}: {exc}",
                traceback=traceback.format_exc(),
            )
        finally:
            _AUTOTUNE["done"] = True


# The padded batch size from which one launch of the MSM program, from
# submission to verdicts, is cheaper than one launch of the
# per-signature program behind the pubkey cache, by device kind
# (jax.devices()[0].device_kind): measured, not derived. An entry is one
# run of scripts/route_prices.py on that kind; PERF.md section 6 prints
# the prices it rests on. On a TPU v5e the MSM's launch was the dearer
# one at every size from 64 rows to MAX_COALESCE_ROWS (14.3 against 5.1
# ms at 128 rows, 59.3 against 57.7 at 8192), so its entry is the first
# padded size past what was measured. A kind without an entry keeps
# crypto/ed25519.py's default. sr25519 shares the number, as it always
# has: it has never run on a chip and no benchmark cell routes it, so it
# has no prices of its own.
MSM_CUTOVER_ROWS = {"TPU v5 lite": 16384}


def _device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def _autotune_probe(dev_pinned: bool, msm_pinned: bool) -> None:
    from ..crypto import ed25519 as ed

    if not (ed._use_device() and ed._accelerator_present()):
        return
    from ..crypto import ed25519_ref as ref
    from . import verify as V

    sk = ref.gen_privkey(b"\x5a" * 32)
    pk, msg = sk[32:], b"tm-engine-autotune-probe"
    sig = ref.sign(sk, msg)
    t0 = _time.perf_counter()
    for _ in range(16):
        ed._single_verify(pk, msg, sig)
    t_host = (_time.perf_counter() - t0) / 16
    jobs = ([pk] * 8, [msg] * 8, [sig] * 8)
    V.verify_batch(*jobs)  # compile + warm
    t0 = _time.perf_counter()
    for _ in range(3):
        V.verify_batch(*jobs)
    t_launch = (_time.perf_counter() - t0) / 3
    # The host route as _dispatch_group runs it (the C loop over one
    # coalesced batch), priced beside the two prices above and deciding
    # nothing yet: the first call loads libcrypto, the second is timed.
    rows = ([pk] * 64, [msg] * 64, [sig] * 64)
    _HOST_VERIFY["ed25519"](*rows)
    t0 = _time.perf_counter()
    _HOST_VERIFY["ed25519"](*rows)
    t_host_route = (_time.perf_counter() - t0) / 64
    cutover = 8
    while cutover * t_host < t_launch and cutover < 4096:
        cutover *= 2
    if not dev_pinned:
        ed.DEVICE_BATCH_CUTOVER = cutover
    if not msm_pinned:
        # none of the prices above decides this: the table's entry for
        # the device kind, as the smallest batch that pads to it
        entry = MSM_CUTOVER_ROWS.get(_device_kind())
        if entry is not None:
            ed.MSM_BATCH_CUTOVER = entry // 2 + 1
    m = _engine_metrics()
    m.autotuned.set(1)
    m.device_batch_cutover.set(ed.DEVICE_BATCH_CUTOVER)
    m.msm_batch_cutover.set(ed.MSM_BATCH_CUTOVER)
    m.autotune_host_sig_seconds.set(t_host)
    m.autotune_launch_seconds.set(t_launch)
    m.autotune_host_route_sig_seconds.set(t_host_route)


# ------------------------------------------------------------------- engine


class _Job:
    __slots__ = (
        "plane", "pks", "msgs", "sigs", "n", "event", "result", "error",
        "flow", "span", "req", "t_submit", "journey", "call",
    )

    def __init__(self, plane, pks, msgs, sigs, journey=None, call=None):
        self.plane = plane
        # the submit_together call that queued it, shared by every job of
        # that call (a token: a lone job is a call of its own)
        self.call = object() if call is None else call
        self.pks = pks
        self.msgs = msgs
        self.sigs = sigs
        self.n = len(sigs)
        self.event = threading.Event()
        self.result: list[bool] | None = None
        self.error: BaseException | None = None
        # trace correlation id linking this job's submit span to the
        # dispatch/collect spans of whichever coalesced launch carries
        # it (0 when tracing is off — new_flow() skipped)
        self.flow = 0
        # the submitter's engine.submit span and its request (trace
        # args.span / args.req; 0 when tracing is off): what the
        # workers' spans name as their parent on another thread
        self.span = self.req = 0
        self.t_submit = 0.0
        # tmpath journey tag (trace.journey_key string or None): rides
        # the job through coalescing so the launch's dispatch/collect
        # spans list which chain events (heights) it verified — the
        # attribution lens/journey.py uses to split verify time
        # host-vs-engine per height even when launches coalesce several
        # heights (docs/observability.md#tmpath)
        self.journey = journey


class JobHandle:
    """Returned by VerifyEngine.submit; result() blocks until the
    coalesced launch containing this job completes and returns the
    job's own per-signature bools (demuxed)."""

    __slots__ = ("_job",)

    def __init__(self, job: _Job):
        self._job = job

    def done(self) -> bool:
        return self._job.event.is_set()

    def result(self, timeout: float | None = None) -> list[bool]:
        if not self._job.event.wait(timeout):
            raise TimeoutError("verification engine result timed out")
        if self._job.error is not None:
            # raise a COPY: every coalesced caller shares one exception
            # instance, and raising the same object from several threads
            # concurrently mutates its __traceback__ (one caller's log
            # would show another caller's raise frames)
            import copy

            try:
                err = copy.copy(self._job.error)
            except Exception:  # exotic exception, uncopyable: share it
                err = self._job.error
            raise err
        return self._job.result


def _caused_by(group) -> dict:
    """Trace args for a worker's span over a group: the oldest job's
    submit span as parent, its request, and every request served when
    the group was coalesced. Empty for jobs submitted with tracing off."""
    first = group[0]
    if not first.span:
        return {}
    caused = {"parent": first.span, "req": first.req}
    if len(group) > 1:
        caused["reqs"] = [j.req for j in group]
    return caused


def _fail_jobs(jobs, exc: BaseException) -> None:
    for j in jobs:
        j.error = exc
        j.event.set()


def _host_verify_ed25519(pks, msgs, sigs) -> list[bool]:
    """Coalesced host-path ed25519: the C libcrypto loop (GIL-free,
    multicore) with the ZIP-215 oracle re-checking only rejected rows —
    the exact acceptance chain of _single_verify, batched."""
    from ..crypto import ed25519_ref as _ref
    from ..crypto.ed25519 import _single_verify
    from ..native import host_verify_batch

    bitmap = host_verify_batch(pks, msgs, sigs)
    if bitmap is None:
        return [_single_verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    out = bitmap.tolist()
    for i, ok in enumerate(out):
        if not ok:
            # may still be ZIP-215-acceptable — ask the oracle directly:
            # OpenSSL already rejected this row, so _single_verify's
            # OpenSSL-first chain would just repeat that verdict
            out[i] = _ref.verify(pks[i], msgs[i], sigs[i], zip215=True)
    return out


def _host_verify_sr25519(pks, msgs, sigs) -> list[bool]:
    from ..crypto import sr25519 as sr

    return [sr.verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)]


_HOST_VERIFY = {"ed25519": _host_verify_ed25519, "sr25519": _host_verify_sr25519}

_HOST_POOL = None
_HOST_POOL_LOCK = threading.Lock()


def _host_pool():
    """Shared executor for host-plane batches: the verify starts at
    DISPATCH time (overlapping whatever the collector is blocked on)
    instead of serializing on the collect thread — a slow pure-Python
    sr25519 loop must not head-of-line-block a finished device batch's
    demux behind it."""
    global _HOST_POOL
    if _HOST_POOL is None:
        with _HOST_POOL_LOCK:
            if _HOST_POOL is None:
                from concurrent.futures import ThreadPoolExecutor

                _HOST_POOL = ThreadPoolExecutor(
                    max_workers=2, thread_name_prefix="ThreadPoolExecutor-engine-host"
                )
    return _HOST_POOL


class VerifyEngine:
    """Process-wide coalescing verification pipeline.

    Two worker threads form the double buffer:
      dispatch — drains the submission queue, coalesces same-plane jobs
                 (bounded by MAX_COALESCE_ROWS), runs host prep and the
                 ASYNC kernel launch (or schedules the host C verify),
                 and hands the in-flight batch to the collector. While
                 the collector blocks on batch i's device result, this
                 thread is already prepping + launching batch i+1.
      collect  — blocks on the device result (or runs the host verify),
                 demuxes the combined bitmap back to per-caller slices,
                 and wakes the callers.

    Threads are daemons, started lazily on first submit, and named with
    the tm-engine prefix (allow-listed by utils/leaktest.py — engine
    lifetime is the process, not a test body)."""

    def __init__(self, mesh=None):
        # the chips a large group is split over (the sharded route):
        # the process's TPU chips, found on first use, unless a mesh is
        # handed in (the tests' virtual CPU devices)
        self._mesh, self._mesh_found = mesh, mesh is not None
        self._lock = threading.Lock()
        self._have_jobs = threading.Condition(self._lock)
        self._pending: list[_Job] = []
        self._inflight: list = []  # (jobs, collect_thunk, path, t_dispatch)
        self._have_inflight = threading.Condition()
        self._started = False
        # Pipeline-overlap accounting: dispatch-stage and host-verify
        # wall intervals land here (bounded); each finished collect sums
        # its own interval's intersection with them — the cumulative
        # dispatch/collect overlap the double buffer exists to create.
        from collections import deque

        self._stage_ivs: deque = deque(maxlen=64)  # (batch_seq, t0, t1)
        # Guards _stage_ivs append/snapshot: the dispatch thread and
        # host-pool workers append while the collect thread iterates,
        # and CPython raises "deque mutated during iteration" on an
        # unlocked snapshot.
        self._stage_ivs_lock = threading.Lock()
        self._overlap_total = 0.0
        self._collect_total = 0.0
        self._seq = 0  # dispatch-thread-only batch counter

    # ------------------------------------------------------------ lifecycle

    def _ensure_started(self) -> None:
        if self._started:
            return
        with self._lock:
            if self._started:
                return
            self._started = True
            for name, fn in (("tm-engine-dispatch", self._dispatch_loop),
                             ("tm-engine-collect", self._collect_loop)):
                t = threading.Thread(target=fn, daemon=True, name=name)
                t.start()

    # -------------------------------------------------------------- submit

    def submit(self, plane: str, pubkeys, msgs, sigs, journey=None) -> JobHandle:
        """Queue one caller's batch for the next coalesced launch.
        plane is "ed25519" or "sr25519"; returns a JobHandle whose
        result() yields this caller's bools in input order. `journey`
        optionally tags the job with a tmpath journey key so the
        coalesced launch's spans stay height-attributable. The one-job
        case of submit_together."""
        return self.submit_together([(plane, pubkeys, msgs, sigs, journey)])[0]

    def submit_together(self, batches) -> list[JobHandle]:
        """Queue several batches, each (plane, pubkeys, msgs, sigs,
        journey), under one acquisition of the lock, with one wake-up
        of the dispatch thread: _take_group then sees all of them, so
        batches of one plane that fit MAX_COALESCE_ROWS together are one
        launch even when the dispatch thread was idle, where two submit
        calls a few hundred microseconds apart are two. Nothing waits
        for a job that may never come: a caller with both its batches
        in hand says so here, and every other caller's latency is what
        it was. One JobHandle a batch, in order, each yielding its own
        rows' bools. The group's route is chosen on its total rows, as
        for any coalesced group, so two batches under
        DEVICE_BATCH_CUTOVER may together pass it and launch. Every
        batch is checked before any is queued: a ragged or unknown one
        raises and nothing was submitted."""
        jobs, call = [], object()
        for plane, pubkeys, msgs, sigs, journey in batches:
            if plane not in _HOST_VERIFY:
                raise ValueError(f"unknown verification plane {plane!r}")
            job = _Job(plane, list(pubkeys), list(msgs), list(sigs), journey=journey, call=call)
            if len(job.pks) != job.n or len(job.msgs) != job.n:
                # ragged inputs would silently truncate in the verify
                # planes' zip()s, reporting unverified tail rows as accepted
                # and shifting later coalesced callers' demux slices
                raise ValueError(
                    f"ragged batch: {len(job.pks)} pubkeys / {len(job.msgs)} msgs "
                    f"/ {job.n} sigs"
                )
            if job.n == 0:
                job.result = []
                job.event.set()
            jobs.append(job)
        queued = [job for job in jobs if job.n]
        if not queued:
            return [JobHandle(job) for job in jobs]
        self._ensure_started()
        m = _engine_metrics()
        now = _time.monotonic()
        together = len(queued) > 1
        for job in queued:
            job.t_submit = now
            if _trace.enabled():
                job.flow = _trace.new_flow()
                sub_args = {"plane": job.plane, "rows": job.n, "flow": job.flow}
                if job.journey:
                    sub_args["journey"] = job.journey
                if together:
                    sub_args["together"] = len(queued)
                with _trace.span("engine.submit", "engine", **sub_args) as sp:
                    job.span, job.req = sp.id, sp.req
            m.submitted_jobs.add(1, job.plane)
            m.submitted_sigs.add(job.n, job.plane)
            if together:
                m.jobs_submitted_together.add(1, job.plane)
        with self._lock:
            self._pending += queued
            # gauge set under the lock: an unlocked set here can lose
            # the race against the dispatch worker's set and leave a
            # phantom backlog on the scrape
            m.queue_depth.set(len(self._pending))
            self._have_jobs.notify()
        return [JobHandle(job) for job in jobs]

    # ------------------------------------------------------------ dispatch

    def _take_group(self):
        """Pop a coalescable group: the oldest pending job plus queued
        jobs on its plane, up to MAX_COALESCE_ROWS. The jobs of the
        oldest job's submit_together call come along in order as far as
        the cap allows. Another call's jobs (a lone submit is a call of
        one) join all together or not at all, and only where the group
        then loads no program (_loads_nothing); else they wait for a
        later group at their own bucket. Returns (group, held), held the
        jobs kept back for that alone. Called with the lock held."""
        first = self._pending[0]
        plane = first.plane
        joined, rows = set(), 0
        for j in self._pending:
            if j.call is not first.call or j.plane != plane:
                continue
            if not joined or rows + j.n <= MAX_COALESCE_ROWS:
                joined.add(j)
                rows += j.n
        held, seen = 0, {first.call}
        for j in self._pending:
            if j.plane != plane or j.call in seen:
                continue
            seen.add(j.call)
            call = [k for k in self._pending if k.call is j.call and k.plane == plane]
            n = sum(k.n for k in call)
            if rows + n > MAX_COALESCE_ROWS:
                continue
            if self._loads_nothing(plane, rows + n):
                joined.update(call)
                rows += n
            else:
                held += len(call)
        group = [j for j in self._pending if j in joined]
        self._pending = [j for j in self._pending if j not in joined]
        return group, held

    def _loads_nothing(self, plane: str, rows: int) -> bool:
        """Whether a group of `rows` on `plane` launches without loading
        a program: at a bucket some launch of the plane has already run
        at in this process (ops/launched.py), or on a route that has no
        bucket to load, the host's or the one split across the chips."""
        from ..crypto import ed25519 as ed
        from . import launched

        if launched.used(plane, launched.pad_pow2(rows)):
            return True
        try:
            if not (ed._use_device() and rows >= ed.DEVICE_BATCH_CUTOVER):
                return True
            mesh = self._split_mesh()
        except Exception:  # noqa: BLE001 - the group's dispatch meets it and delivers it
            return True
        return mesh is not None and splits(mesh.devices.size, rows)

    def _dispatch_loop(self) -> None:
        while True:
            m = _engine_metrics()
            with self._lock:
                while not self._pending:
                    self._have_jobs.wait()
                with _trace.span("engine.coalesce", "engine") as sp:
                    group, held = self._take_group()
                    sp.annotate(held=held)
                m.queue_depth.set(len(self._pending))
            if held:
                m.coalesce_held_jobs.add(held, group[0].plane)
            rows = sum(j.n for j in group)
            t0 = _time.monotonic()
            # metric writes never raise (metrics._never_raise), so none
            # of these can kill the dispatch worker
            m.coalesced_group_size.observe(len(group))
            m.coalesce_factor.observe(rows)
            m.queue_wait.observe(t0 - group[0].t_submit)
            self._seq += 1
            seq = self._seq
            sp = _trace.span(
                "engine.dispatch", "engine",
                plane=group[0].plane, jobs=len(group), rows=rows,
                flow=group[0].flow, **_caused_by(group),
            )
            journeys = sorted({j.journey for j in group if j.journey})
            if journeys:
                sp.annotate(journeys=journeys)
            try:
                with sp:
                    maybe_autotune()  # no-op after the first group
                    thunk, path = self._dispatch_group(group, seq)
                    sp.annotate(path=path)
            except BaseException as e:  # noqa: BLE001 - deliver, don't die
                _fail_jobs(group, e)
                continue
            t1 = _time.monotonic()
            m.launch_latency.observe(t1 - t0)
            with self._stage_ivs_lock:
                self._stage_ivs.append((seq, t0, t1))
            with self._have_inflight:
                self._inflight.append((group, thunk, path, seq))
                m.inflight_batches.set(len(self._inflight))
                self._have_inflight.notify()

    def _split_mesh(self):
        if not self._mesh_found:
            self._mesh, self._mesh_found = _local_tpu_mesh(), True
        return self._mesh

    def _dispatch_group(self, group, seq: int = 0):
        """Coalesce one group's rows, decide the plane (split across the
        chips / device bitmap / two-phase MSM / host C), run prep + the
        async launch NOW, and return (collect thunk producing the
        combined (rows,) bools, path name for telemetry). seq tags this
        batch's recorded stage intervals so its own collect never counts
        them as overlap."""
        from ..crypto import ed25519 as ed

        plane = group[0].plane
        flow = group[0].flow
        caused = _caused_by(group)
        pks, msgs, sigs = [], [], []
        for j in group:
            pks += j.pks
            msgs += j.msgs
            sigs += j.sigs
        total = len(sigs)

        if not (ed._use_device() and total >= ed.DEVICE_BATCH_CUTOVER):
            host_fn = _HOST_VERIFY[plane]

            def host_verify():
                m = _engine_metrics()
                m.host_pool_active.add(1)
                t0 = _time.monotonic()
                try:
                    with _trace.span("engine.host_verify", "engine",
                                     plane=plane, rows=total, flow=flow, **caused):
                        return host_fn(pks, msgs, sigs)
                finally:
                    # metric writes never raise; nothing here can mask
                    # a real host_fn error through future.result
                    t1 = _time.monotonic()
                    m.host_pool_active.add(-1)
                    m.host_pool_busy_seconds.add(t1 - t0)
                    with self._stage_ivs_lock:
                        self._stage_ivs.append((seq, t0, t1))

            future = _host_pool().submit(host_verify)
            return future.result, "host"  # .result raises the worker's exception

        mesh = self._split_mesh()
        if mesh is not None and splits(mesh.devices.size, total):
            from ..parallel import sharded_verify as sharded

            handle = sharded.dispatch(mesh, pks, msgs, sigs, plane)
            if handle is not None:  # else the mesh cache is full: one chip's routes
                return (lambda: [bool(b) for b in sharded.collect(handle)]), "sharded"

        if plane == "ed25519":
            from . import verify as dev
        else:
            from . import verify_sr as dev

        def bitmap_async():
            if ed._pk_cache_enabled():
                return dev.verify_batch_cached_async(pks, msgs, sigs)
            return dev.verify_batch_async(pks, msgs, sigs)

        if total >= ed.MSM_BATCH_CUTOVER:
            # two-phase: the RLC/MSM all-valid fast path first, the
            # bitmap kernel only on failure — the reference's shape
            # (types/validation.go:245-255). A precheck refusal (None
            # handle) dispatches the bitmap immediately, preserving the
            # launch-now/collect-later overlap.
            from . import msm as dev_msm

            if plane == "sr25519":
                rlc = dev_msm.verify_batch_rlc_sr_async(pks, msgs, sigs)
            else:
                rlc = dev_msm.verify_batch_rlc_async(pks, msgs, sigs)
            dispatched = bitmap_async() if rlc is None else None

            def collect_two_phase():
                if rlc is not None and dev_msm.collect_rlc(rlc):
                    return [True] * total
                handle = dispatched if dispatched is not None else bitmap_async()
                return [bool(b) for b in dev.collect(handle)]

            return collect_two_phase, "two_phase_msm"

        dispatched = bitmap_async()
        return (lambda: [bool(b) for b in dev.collect(dispatched)]), "bitmap"

    # ------------------------------------------------------------- collect

    def _collect_loop(self) -> None:
        while True:
            m = _engine_metrics()
            with self._have_inflight:
                while not self._inflight:
                    self._have_inflight.wait()
                group, thunk, path, seq = self._inflight.pop(0)
                # same lock discipline as queue_depth: serialize the
                # gauge write with the list state it describes
                m.inflight_batches.set(len(self._inflight))
            rows = sum(j.n for j in group)
            t0 = _time.monotonic()
            try:
                c_args = {"plane": group[0].plane, "jobs": len(group),
                          "rows": rows, "path": path, "flow": group[0].flow,
                          **_caused_by(group)}
                journeys = sorted({j.journey for j in group if j.journey})
                if journeys:
                    c_args["journeys"] = journeys
                with _trace.span("engine.collect", "engine", **c_args):
                    bools = thunk()
                # materialize + validate inside the guard: a None/
                # generator/short bitmap from a buggy verify path must
                # fail the group, not kill this worker — and a short
                # slice-truncation below would make all([]) == True
                # report unverified rows as accepted
                bools = list(bools)
                if len(bools) != rows:
                    raise RuntimeError(
                        f"verify path {path!r} returned {len(bools)} "
                        f"results for {rows} rows")
            except BaseException as e:  # noqa: BLE001
                _fail_jobs(group, e)
                continue
            t1 = _time.monotonic()
            lo = 0
            for j in group:
                j.result = bools[lo : lo + j.n]
                lo += j.n
                j.event.set()
            # Telemetry only after every caller is woken: a bookkeeping
            # bug must neither strand an already-verified group nor kill
            # this worker (which would hang every future submit).
            try:
                m.collect_latency.observe(t1 - t0)
                self._account_overlap(m, seq, t0, t1)
                m.observe_path(group[0].plane, path, bools)
            except Exception:  # noqa: BLE001
                pass

    def _account_overlap(self, m, seq: int, c0: float, c1: float) -> None:
        """Fold one collect interval's intersection with OTHER batches'
        recorded dispatch/host-verify intervals into the overlap
        telemetry (own-batch intervals excluded: blocking on your own
        launch is latency, not pipeline overlap). The other-batch
        intervals are unioned before measuring, so two host verifies
        running inside the same collect window count once and the
        ratio stays <= 1 ("fraction of collect time the pipeline was
        also doing other work"). Stages still running when the collect
        ends are not yet in _stage_ivs and go uncounted — overlap is a
        floor, not a ceiling. Runs only on the collect worker, so the
        accumulators need no lock; the _stage_ivs snapshot takes
        _stage_ivs_lock because dispatch/host workers append
        concurrently and deque iteration during mutation raises."""
        with self._stage_ivs_lock:
            ivs = list(self._stage_ivs)
        clipped = sorted(
            (max(c0, s), min(c1, e))
            for iv_seq, s, e in ivs
            if iv_seq != seq and s < c1 and e > c0
        )
        overlap = 0.0
        cur_s = cur_e = None
        for s, e in clipped:
            if cur_e is None or s > cur_e:
                if cur_e is not None:
                    overlap += cur_e - cur_s
                cur_s, cur_e = s, e
            elif e > cur_e:
                cur_e = e
        if cur_e is not None:
            overlap += cur_e - cur_s
        self._overlap_total += overlap
        self._collect_total += c1 - c0
        if overlap:
            m.overlap_seconds.add(overlap)
        if self._collect_total > 0:
            m.overlap_ratio.set(self._overlap_total / self._collect_total)


_ENGINE: VerifyEngine | None = None
_ENGINE_LOCK = threading.Lock()


def get_engine() -> VerifyEngine:
    global _ENGINE
    if _ENGINE is None:
        with _ENGINE_LOCK:
            if _ENGINE is None:
                _ENGINE = VerifyEngine()
    return _ENGINE


def verify_async_via_engine(plane: str, pubkeys, msgs, sigs, journey=None):
    """The BatchVerifier.verify_async seam, shared by both signature
    planes: submit to the engine, return a completion callable yielding
    the (all_ok, per-signature bools) contract. `journey` tags the job
    for tmpath height attribution (see VerifyEngine.submit)."""
    return verify_together_via_engine([(plane, pubkeys, msgs, sigs, journey)])[0]


def verify_together_via_engine(batches) -> list:
    """verify_async_via_engine for several batches handed over in one
    call (VerifyEngine.submit_together): one completion callable a
    batch, in order."""
    return [functools.partial(_complete, h) for h in get_engine().submit_together(batches)]


def _complete(handle: JobHandle):
    bools = handle.result()
    return all(bools), bools
