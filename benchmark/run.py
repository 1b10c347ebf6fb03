"""The benchmark's one command.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name: `BENCHMARK.json` names
the cell's configuration (`benchmark/configs/<name>.json`) and traffic
mix (`benchmark/traffic/<name>.json`, whose `driver` is a module of
`benchmark/drivers/`), its end-to-end metrics and its per-layer metrics
(each a reader, `benchmark/metrics/<name>.py`). Nothing here names a
cell, a configuration, a mix or a metric.

One process, the only one that touches JAX. Without a TPU, or with
fewer chips than the cell asks for, it exits 3 and prints no result.
Set-up (import, backend, native build, autotune, chain, programs,
warm-up) runs to the start of the window and is reported as `setup_s`;
the window then measures for `--seconds`; `correct` is decided after it
has closed, against `benchmark/reference.py`. The last line of stdout
is the result; what was compared, beside its limits, ends stderr.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_RING = 1 << 21  # events the program's span ring holds in a traced run
SLICE_S = 1.25  # the profiler's slice: the window's last seconds, or its last third
SLICE_END_S = 0.5  # ... less this, so that the slice closes inside the window


class Refused(Exception):
    """The run cannot be made here; carries the process's exit code."""

    def __init__(self, code: int, why: str):
        super().__init__(why)
        self.code = code


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(root: str, *parts: str):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused(2, f"no {what} named {name!r} in BENCHMARK.json")


def reported_in(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile of all the values, none dropped."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, max(0, -(-len(ordered) * q // 100) - 1))]


def engine_snapshot() -> dict:
    from tendermint_tpu.metrics import engine_metrics

    out = {}
    for family in vars(engine_metrics()).values():
        if hasattr(family, "samples"):
            for name, labels, value in family.samples():
                out[(name, tuple(sorted(labels.items())))] = value
    return out


def cutovers() -> dict:
    from tendermint_tpu.crypto import ed25519 as ed

    return {"device": ed.DEVICE_BATCH_CUTOVER, "msm": ed.MSM_BATCH_CUTOVER}


class Tracer:
    """The traced run's slice: a `jax.profiler` trace of a few seconds
    inside the window, with the engine's counters read at its two ends
    and one one-element device op of the harness's own (the anchor)
    launched inside it. The anchor shows that the device's plane was
    recorded, so that an empty plane means an idle chip and not a lost
    trace; its few microseconds are part of `busy_s` and are left out of
    every kernel metric."""

    def __init__(self, log_dir: str, keep: str | None = None):
        import jax
        import jax.numpy as jnp

        self.log_dir, self.keep = log_dir, keep

        def bench_anchor(x):
            return x + 1

        self.anchor = jax.jit(bench_anchor).lower(
            jax.ShapeDtypeStruct((8,), jnp.int32)).compile()
        self.anchor_arg = jnp.zeros((8,), jnp.int32)
        self.anchor(self.anchor_arg).block_until_ready()
        self.thread = None
        self.error = None
        self.tracing = False
        self.t0_ns = self.t1_ns = self.marker_pc_ns = None
        self.before = self.after = None

    def start(self, window_seconds: float) -> None:
        self.thread = threading.Thread(target=self._run, args=(window_seconds,),
                                       name="bench-tracer", daemon=True)
        self.thread.start()

    def _run(self, window_seconds: float) -> None:
        """Open the slice near the window's end and close it inside the
        window. The profiler itself is stopped by finish(), once the
        window has closed: turning two million device events into a
        trace file takes most of a minute, and five times that beside a
        running window."""
        try:
            from jax import profiler

            from benchmark import reducer

            length = min(SLICE_S, window_seconds / 3)
            time.sleep(max(0.0, window_seconds - length - SLICE_END_S))
            opts = profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            profiler.start_trace(self.log_dir, profiler_options=opts)
            self.tracing = True
            self.marker_pc_ns = time.perf_counter_ns()
            with profiler.TraceAnnotation(reducer.MARKER):
                pass
            self.before = engine_snapshot()
            self.t0_ns = time.perf_counter_ns()
            time.sleep(0.005)  # the device's clock runs up to a millisecond off the host's
            self.anchor(self.anchor_arg).block_until_ready()
            time.sleep(length)
            self.t1_ns = time.perf_counter_ns()
            self.after = engine_snapshot()
        except BaseException as e:  # noqa: BLE001 - reported by finish()
            self.error = e

    def finish(self) -> dict:
        """Spans and device numbers of the slice."""
        from tendermint_tpu import trace

        from benchmark import reducer

        from jax import profiler

        self.thread.join(timeout=60.0)
        t = time.time()
        if self.tracing:
            profiler.stop_trace()
        if self.error is not None or self.t1_ns is None:
            raise RuntimeError(f"the traced slice failed: {self.error!r}")
        stop_s = time.time() - t
        events = [ev for ev in trace.export()["traceEvents"] if ev.get("ph") == "X"]
        if len(events) >= TRACE_RING - 1:
            raise RuntimeError(f"the span ring wrapped ({len(events)} events): spans were dropped")
        spans = []
        for ev in events:
            t0, t1 = ev["ts"] * 1e3, (ev["ts"] + ev["dur"]) * 1e3
            if t1 <= self.t0_ns or t0 >= self.t1_ns:
                continue
            spans.append({"name": ev["name"], "cat": ev.get("cat", ""), "tid": ev["tid"],
                          "t0": max(t0, self.t0_ns), "t1": min(t1, self.t1_ns),
                          "ends_in_slice": t1 <= self.t1_ns, "args": ev.get("args", {})})
        t = time.time()
        xplane = reducer.find_xplane(self.log_dir)
        xplane_bytes = os.path.getsize(xplane)
        extracted = reducer.extract(xplane)
        shutil.rmtree(self.log_dir, ignore_errors=True)
        device = None
        if extracted["marker_ns"] is not None:
            shift = extracted["marker_ns"] - self.marker_pc_ns  # perf_counter -> profile clock
            if self.keep:
                with open(self.keep, "w") as f:
                    json.dump(dict(extracted, t0_ns=self.t0_ns + shift,
                                   t1_ns=self.t1_ns + shift), f)
            program_spans = [{"name": sp["name"], "t0": sp["t0"] + shift, "t1": sp["t1"] + shift}
                             for sp in spans if sp["cat"] != "bench"]
            device = reducer.reduce(extracted, self.t0_ns + shift, self.t1_ns + shift,
                                    program_spans)
        log(f"trace: {len(events)} spans in the ring, {len(spans)} in the slice of "
            f"{(self.t1_ns - self.t0_ns) / 1e9:.3f}s; profiler stopped in {stop_s:.3f}s; profile of "
            f"{xplane_bytes} bytes read in "
            f"{time.time() - t:.3f}s, lines: {extracted['lines']}")
        return {"spans": spans, "device": device,
                "counters": {"before": self.before, "after": self.after}}


class Harness:
    """One cell in one process: what is found by name, then the
    program's import, the device, the native library and the autotune
    probe. `run()` makes one measurement; `benchmark/tools` makes
    several, on several seeds, in one process. `require_tpu` and `root`
    (where BENCHMARK.json and the data files are read from) are for the
    rehearsals in benchmark/tests, on XLA:CPU at a tiny size."""

    def __init__(self, workload: str, traced: bool, require_tpu: bool = True, root: str = ROOT):
        self.root, self.traced, self.require_tpu = root, traced, require_tpu
        self.bench = bench = load_json(root, "BENCHMARK.json")
        self.cell = cell = by_name(bench["workloads"], workload, "workload")
        self.config = load_json(root, by_name(bench["configs"], cell["config"],
                                              "configuration")["file"])
        self.params = load_json(root, "benchmark", "traffic", cell["traffic"] + ".json")
        self.driver = importlib.import_module("benchmark.drivers." + self.params["driver"])
        self.readers = {
            m["name"]: importlib.import_module("benchmark.metrics." + m["name"].split(".")[0])
            for m in bench["per_layer"] if reported_in(m, cell["name"])}
        # The program's documented operator settings, where the
        # configuration pins any (see `assumed` there), and the span
        # ring's size: both are read when the program is imported.
        for key, value in self.config.get("env", {}).items():
            os.environ[key] = str(value)
        if traced:
            os.environ["TM_TPU_TRACE_BUF"] = str(TRACE_RING)
        try:
            import tendermint_tpu  # noqa: F401
        except ImportError as e:
            raise Refused(2, f"the program is not in this checkout: {e}") from e

        import jax

        devices = jax.devices()
        if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
            raise Refused(3, f"{workload} needs {cell['chips']} TPU chip(s); jax.devices() gives "
                             f"{len(devices)} x {devices[0].platform}:{devices[0].device_kind}")
        self.devices = devices[: cell["chips"]]
        self.device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                       "count": len(self.devices)}
        self.peaks = load_json(root, "benchmark", "peaks.json").get(self.device["kind"])
        if self.peaks is None and require_tpu:
            raise Refused(3, f"no peaks for device_kind {self.device['kind']!r} in "
                             "benchmark/peaks.json")

        from tendermint_tpu import devobs, native
        from tendermint_tpu.ops import enable_compile_cache, engine

        cache_dir = enable_compile_cache()
        devobs.install()
        if native.load_prep() is None:
            raise Refused(2, "the native prep library did not build or load")
        log(f"device: {self.device}  jax {jax.__version__}  compile cache: {cache_dir} "
            f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} entries)")
        t = time.time()
        engine.maybe_autotune()
        log(f"autotune: {time.time() - t:.3f}s cutovers {cutovers()} "
            f"pinned {self.config.get('env', {})}")

    def run(self, seed: int, seconds: float, keep_trace: str | None = None,
            before_window=None) -> tuple[dict, list]:
        """Build, warm up, measure for `seconds`, decide `correct`.
        Returns the result line's object and the checks. `before_window`
        is called once set-up is over: the tests break the timed path
        there."""
        from tendermint_tpu import devobs, trace

        from benchmark.drivers import Check

        bench, cell, params = self.bench, self.cell, self.params
        traffic = self.driver.Traffic(self.config, params, seed)
        t = time.time()
        traffic.build()
        log(f"build: {time.time() - t:.3f}s")
        t = time.time()
        traffic.warm_up()
        log(f"warm-up: {time.time() - t:.3f}s")
        tracer = None
        if self.traced:
            tracer = Tracer(os.path.join(ROOT, ".bench_traces", cell["name"]), keep_trace)
            trace.set_enabled(True)
            trace.clear()
        if before_window is not None:
            before_window()
        # Set-up's objects (a chain is millions of them) out of the
        # collector's sight, or every full collection in the window walks them.
        gc.collect()
        gc.freeze()
        rows_setup = engine_snapshot()
        devobs_start = devobs.status(tail=256)
        setup_s = time.time() - T_START
        log(f"set-up: {setup_s:.3f}s; programs: "
            + ", ".join(f"{ev['fn']}@{ev['rows']} {ev['dur_s']}s" for ev in devobs_start["tail"]
                        if ev["dur_s"] >= 0.5))

        if tracer is not None:
            tracer.start(seconds)
        window = traffic.window(seconds)
        devobs_end = devobs.status(tail=256)
        rows_end = engine_snapshot()
        traced = tracer.finish() if tracer is not None else None
        trace.set_enabled(False)
        device = dict(self.device)
        device["memory_peak_bytes"] = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices)
        in_window = {k: v - rows_setup.get(k, 0) for k, v in rows_end.items()
                     if "path_rows_total" in k[0] and v != rows_setup.get(k, 0)}
        log(f"window: {window['window_s']:.3f}s, {window['ops']} operations, "
            + ", ".join(f"{k}={v}" for k, v in window.items()
                        if k not in ("ops", "window_s", "latencies_ms"))
            + f"; cutovers {cutovers()}; rows by route: "
            + json.dumps({dict(k[1])["path"] + "/" + dict(k[1])["status"]: v
                          for k, v in in_window.items()}))
        if window.get("latencies_ms"):
            lat = window["latencies_ms"]
            log(f"latencies: n={len(lat)} p50={statistics.median(lat):.3f}ms "
                f"p95={percentile(lat, 95):.3f}ms max={max(lat):.3f}ms")

        # -- correct: after the window, against the reference
        t = time.time()
        checks, attempted, failed = traffic.check()
        gc.unfreeze()
        log(f"check: {time.time() - t:.3f}s; refusal: "
            f"{json.dumps(getattr(traffic, 'refusal', None))}")
        compiled = devobs_end["compiles"] - devobs_start["compiles"]
        if compiled:
            log("compiled in the window: " + ", ".join(
                f"{ev['fn']}@{ev['rows']} {ev['dur_s']}s" for ev in devobs_end["tail"][-compiled:]))
        checks.append(Check("programs_compiled_in_the_window", compiled, 0))
        checks.append(Check("windows_with_no_operation", 0 if window["ops"] > 0 else 1, 0))

        # -- metrics
        metrics = {}
        if traced is None:
            values = {"setup_s": setup_s}
            for name in params["rate_metrics"]:  # one reading, under each name the cell reports
                values[name] = window["ops"] / window["window_s"]
            for name, q in params.get("latency_percentiles", {}).items():
                if window.get("latencies_ms"):
                    values[name] = percentile(window["latencies_ms"], q)
            for m in bench["end_to_end"]:
                if reported_in(m, cell["name"]) and m["name"] in values:
                    metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        else:
            ctx = dict(traced, window=window, config=self.config, params=params,
                       peaks=self.peaks, cutovers=cutovers(),
                       work=load_json(self.root, "benchmark", "work.json"),
                       devobs={"window_start": devobs_start, "window_end": devobs_end})
            units = {m["name"]: m["unit"] for m in bench["per_layer"]}
            for name, reader in self.readers.items():
                value = reader.read(ctx)
                if value is not None:
                    metrics[name] = {"value": value, "unit": units[name]}
            if traced["device"] is not None and traced["device"]["busy_s"] > 0:
                device["busy_s"] = traced["device"]["busy_s"]
                device["window_s"] = traced["device"]["window_s"]
            elif self.require_tpu:
                raise Refused(4, "the profiler's trace holds no device operation")
        result = {"correct": all(c.ok for c in checks), "attempted": attempted,
                  "failed": failed, "metrics": metrics, "device": device}
        if traced is not None and traced["device"] is not None:
            result["breakdown"] = {"device_ops": traced["device"]["ops"],
                                   "idle_gaps": traced["device"]["gaps"]}
        result["compared"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
        return result, checks


def report(result: dict, checks: list) -> None:
    """What was compared, beside its limits, as the last lines of
    stderr; the result as the last line of stdout."""
    sys.stdout.flush()
    for c in checks:
        print(f"compared {c.name}: {c.value} (limit {c.limit}){'' if c.ok else '  <-- FAILS'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None, require_tpu: bool = True, root: str = ROOT, before_window=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="FILE",
                    help="also write the traced slice's device events, as the reducer "
                         "extracts them, to FILE (how benchmark/tests/data was recorded)")
    args = ap.parse_args(argv)
    try:
        harness = Harness(args.workload, bool(args.trace), require_tpu, root)
        result, checks = harness.run(args.seed, args.seconds, args.keep_trace, before_window)
    except Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return e.code
    report(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
