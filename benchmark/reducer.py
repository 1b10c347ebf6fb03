"""From a `jax.profiler` trace to device busy time, kernel time and idle
gaps. The one reduction every PR's traced run goes through
(`on-chip-measurement` sections 4 and 6).

Two steps, so that the second can be rehearsed without a chip:
`extract(xplane_path)` reads the `.xplane.pb` with nothing but JAX into
plain values (device op events and the harness's clock marker), and
`reduce(extracted, ...)` turns those into numbers. `tests/data/` holds
an extracted trace recorded on the chip.
"""

from __future__ import annotations

import glob
import os

MARKER = "bench.clock_marker"
ANCHOR = "bench_anchor"  # the harness's own one-element device op (see run.py)
# The line of a device plane read: one event per execution of a compiled
# program, from its first operation's start to its last one's end. The
# "XLA Ops" line holds every operation inside (over two million events
# in four seconds of the MSM kernel's loops: minutes to walk in Python,
# a gigabyte as JSON) and is left alone.
OP_LINES = ("XLA Modules",)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def extract(xplane_path: str) -> dict:
    """{"devices": {plane: [[name, start_ns, dur_ns], ...]}, "marker_ns":
    start of the clock marker on the profile's clock or None, "lines":
    {plane: {line: events}}} from one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices: dict[str, list] = {}
    lines: dict[str, dict] = {}
    marker_ns = None
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_device and line.name not in OP_LINES:
                lines.setdefault(plane.name, {})[line.name] = "not read"
                continue
            events = list(line.events)
            lines.setdefault(plane.name, {})[line.name] = len(events)
            if is_device:
                devices.setdefault(plane.name, []).extend(
                    [ev.name, float(ev.start_ns), float(ev.duration_ns)] for ev in events)
            elif marker_ns is None:
                marker_ns = next((float(ev.start_ns) for ev in events if ev.name == MARKER), None)
    return {"devices": devices, "marker_ns": marker_ns, "lines": lines}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _base_name(name: str) -> str:
    """`jit_msm_verify_kernel_impl(1234567)` -> `jit_msm_verify_kernel_impl`:
    the program's fingerprint changes with every recompile."""
    head, _, tail = name.partition("(")
    return head if tail.rstrip(")").isdigit() else name


def reduce(extracted: dict, t0_ns: float, t1_ns: float, spans: list[dict] | None = None,
           top: int = 10) -> dict | None:
    """Numbers of the slice [t0_ns, t1_ns] of the profile's clock.
    `spans` are the program's spans on the same clock ({"name", "t0",
    "t1"} in ns), used only to name the idle gaps. Returns None where
    the trace has no device plane: nothing to read, not zero.

    busy_s     union of the device's op intervals, averaged over devices
    kernel_s   the same without the harness's anchor program
    ops        [[program, seconds]] by total duration, fingerprints stripped
    gaps       [[span name or "no_span", seconds]] of device-idle time,
               by the innermost program span open at that moment
    """
    if not extracted["devices"]:
        return None
    window = t1_ns - t0_ns
    busy_total = kernel_total = 0.0
    by_op: dict[str, float] = {}
    idle: list[tuple[float, float]] = []
    n_ops = 0
    for events in extracted["devices"].values():
        clipped, kernels = [], []
        for name, start, dur in events:
            s, e = max(start, t0_ns), min(start + dur, t1_ns)
            if e <= s:
                continue
            clipped.append((s, e))
            if ANCHOR not in name:
                kernels.append((s, e))
                n_ops += 1
            by_op[_base_name(name)] = by_op.get(_base_name(name), 0.0) + (e - s)
        busy = _union(clipped)
        busy_total += sum(e - s for s, e in busy)
        kernel_total += sum(e - s for s, e in _union(kernels))
        at = t0_ns
        for s, e in busy:
            if s > at:
                idle.append((at, s))
            at = e
        if at < t1_ns:
            idle.append((at, t1_ns))
    n_dev = len(extracted["devices"])
    gaps: dict[str, float] = {}
    if spans is not None:
        for name, ns in _attribute(idle, spans).items():
            gaps[name] = ns / n_dev
    return {
        "window_s": window / 1e9,
        "busy_s": busy_total / n_dev / 1e9,
        "kernel_s": kernel_total / n_dev / 1e9,
        "device_op_events": n_ops,
        "ops": [[k, v / n_dev / 1e9] for k, v in
                sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "gaps": [[k, v / 1e9] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
    }


def _attribute(idle: list[tuple[float, float]], spans: list[dict]) -> dict[str, float]:
    """Idle nanoseconds by the innermost span open at each moment: of
    the spans covering it, the one that started last, on any thread."""
    out: dict[str, float] = {}
    spans = sorted(spans, key=lambda sp: sp["t0"])
    for g0, g1 in idle:
        open_here = [sp for sp in spans if sp["t0"] < g1 and sp["t1"] > g0]
        cuts = sorted({t for sp in open_here for t in (sp["t0"], sp["t1"]) if g0 < t < g1})
        points = [g0] + cuts + [g1]
        for a, b in zip(points, points[1:]):
            mid, name = (a + b) / 2, "no_span"
            for sp in open_here:
                if sp["t0"] > mid:
                    break
                if sp["t1"] > mid:
                    name = sp["name"]
            out[name] = out.get(name, 0.0) + (b - a)
    return out
