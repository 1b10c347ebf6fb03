"""tmdev — the device-plane observatory (tendermint_tpu/devobs/,
lens/device.py, docs/observability.md#tmdev).

Runtime half: listener attribution, transfer accounting, lifecycle
(install is idempotent and never raises; a stubbed/absent
jax.monitoring degrades to a warn-once no-op WITHOUT breaking the
node import chain — pinned in a subprocess). The compile listener is
driven directly (`_on_duration`) so the tests never pay a real XLA
compile.

Analysis half: device digests from real expositions (rendered by the
same Registry.gather a node serves), the shared trip conditions, and
the recompile_storm / device_mem_growth gates end to end through
analyze_run — including their vacuous pass when no node exposed
device evidence.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tendermint_tpu import devobs
from tendermint_tpu import trace as T
from tendermint_tpu.lens import analyze_run, parse_exposition
from tendermint_tpu.lens.device import (
    LIVE_BUFFER_SERIES,
    device_digest,
    mem_growth_offenders,
    recompile_offenders,
)
from tendermint_tpu.metrics import DeviceMetrics, Registry

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _starts_uninstalled():
    """Every test here starts from an uninstalled observatory and leaves
    one: the installed flag is the process's, and another file's test on
    this worker (tests/test_perf.py runs `tmperf record`, whose device-obs
    stage installs) must not decide what these see."""
    devobs.uninstall()
    yield
    devobs.uninstall()


@pytest.fixture
def observatory():
    """Installed devobs for one test, always uninstalled after (the
    listener registration is process-global jax state)."""
    assert devobs.install() is True
    try:
        yield devobs
    finally:
        devobs.uninstall()


# ---------------------------------------------------------------- runtime


def test_disabled_hooks_are_free_noops():
    assert not devobs.enabled()
    with devobs.attribution(fn="x", rows=8):
        assert devobs.current_attribution() == {}
    with devobs.transfer_span("h2d", 1024):
        pass
    assert devobs.sample_residency() is None
    st = devobs.status()
    assert st == {"enabled": False, "compiles": 0, "tail": []}
    # a disabled listener invocation is inert, not an error
    devobs._on_duration("/jax/core/compile/backend_compile_duration", 1.0)
    assert devobs.status()["compiles"] == 0


def test_compile_attribution_and_tail(observatory):
    before = devobs.status()["compiles"]
    with devobs.attribution(fn="ed25519_bitmap", rows=512):
        devobs._on_duration(
            "/jax/core/compile/backend_compile_duration", 1.25)
    # non-compile duration events never count
    devobs._on_duration("/jax/some_other_duration", 9.9)
    st = devobs.status()
    assert st["enabled"] and st["compiles"] == before + 1
    rec = st["tail"][-1]
    assert rec["fn"] == "ed25519_bitmap" and rec["rows"] == 512
    assert rec["dur_s"] == pytest.approx(1.25)
    # the metrics registry carries the same cell
    from tendermint_tpu.metrics import device_metrics, global_registry

    device_metrics()
    exp = parse_exposition(global_registry().gather())
    assert exp.total(
        "tendermint_device_bucket_compiles_total",
        fn="ed25519_bitmap", rows="512",
    ) >= 1


def test_attribution_nests_and_is_thread_local(observatory):
    with devobs.attribution(fn="outer", rows=64):
        with devobs.attribution(rows=128):
            assert devobs.current_attribution() == {"fn": "outer", "rows": 128}
        assert devobs.current_attribution() == {"fn": "outer", "rows": 64}
    assert devobs.current_attribution() == {}
    seen = {}
    import threading

    def other():
        seen["ctx"] = devobs.current_attribution()

    with devobs.attribution(fn="main_thread_only"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert seen["ctx"] == {}  # context never leaks across threads


def test_unattributed_compile_still_counts(observatory):
    devobs._on_duration("/jax/core/compile/backend_compile_duration", 0.5)
    assert devobs.status()["tail"][-1]["fn"] == "unattributed"


def test_transfer_span_counts_bytes_and_emits_flow_linked_spans(observatory):
    was = T.enabled()
    T.set_enabled(True)
    T.clear()
    try:
        before = devobs.status()["transfer_bytes"]["h2d"]
        fid = devobs.next_flow()
        with devobs.transfer_span("h2d", 4096, flow=fid):
            pass
        with devobs.transfer_span("d2h", 64, flow=fid):
            pass
        st = devobs.status()
        assert st["transfer_bytes"]["h2d"] == before + 4096
        assert st["transfers"]["d2h"] >= 1
        evs = [e for e in T.export()["traceEvents"]
               if e.get("name") in ("device.h2d", "device.d2h")]
        assert {e["name"] for e in evs} == {"device.h2d", "device.d2h"}
        assert all(e["args"]["flow"] == fid for e in evs)
        # flow arrows synthesized at export tie the pair together
        arrows = [e for e in T.export()["traceEvents"]
                  if e.get("ph") in ("s", "f") and e.get("id") == fid]
        assert len(arrows) >= 2
    finally:
        T.clear()
        T.set_enabled(was)


def test_residency_sampler_counts_live_buffers(observatory):
    import jax.numpy as jnp

    keep = jnp.zeros(1024, dtype=jnp.uint8)  # noqa: F841 - held live on purpose
    s = devobs.sample_residency()
    assert s is not None
    assert s["live_buffer_bytes"] >= 1024
    assert s["high_water_bytes"] >= s["live_buffer_bytes"] or (
        s["high_water_bytes"] >= 1024
    )
    assert devobs.status()["residency_samples"] >= 1


def test_install_is_idempotent_and_uninstall_quiesces():
    assert devobs.install() is True
    assert devobs.install() is True  # second install registers nothing new
    devobs.uninstall()
    assert not devobs.enabled()
    n = devobs.status()["compiles"]
    devobs._on_duration("/jax/core/compile/backend_compile_duration", 1.0)
    assert devobs.status() == {"enabled": False, "compiles": 0, "tail": []}
    devobs.uninstall()  # double-uninstall is a no-op
    assert devobs.status()["compiles"] == 0 or n >= 0


def test_maybe_install_env_gate(monkeypatch):
    monkeypatch.delenv("TM_TPU_DEVOBS", raising=False)
    assert devobs.maybe_install() is None
    assert not devobs.enabled()
    monkeypatch.setenv("TM_TPU_DEVOBS", "1")
    try:
        assert devobs.maybe_install() is True
        assert devobs.enabled()
    finally:
        devobs.uninstall()


def test_monitoring_drift_degrades_to_warn_once_noop():
    """A jax whose monitoring API drifted (register fns gone) must
    yield install() -> None with exactly ONE warning, and every hook
    stays a no-op — run in a subprocess so the stub never touches this
    process's real jax, and so the node import chain (cli) is proven
    to survive the degraded observatory."""
    prog = textwrap.dedent("""
        import sys, types, warnings
        fake_jax = types.ModuleType("jax")
        fake_jax.monitoring = types.ModuleType("jax.monitoring")
        sys.modules["jax"] = fake_jax
        sys.modules["jax.monitoring"] = fake_jax.monitoring
        import os
        os.environ["TM_TPU_DEVOBS"] = "1"
        from tendermint_tpu import devobs
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert devobs.maybe_install() is None
            assert devobs.install() is None  # still degraded, still quiet
            assert not devobs.enabled()
        assert len(w) == 1, [str(x.message) for x in w]
        assert "devobs" in str(w[0].message)
        with devobs.attribution(fn="x"):
            pass
        with devobs.transfer_span("h2d", 10):
            pass
        assert devobs.sample_residency() is None
        # the node entrypoint module still imports under the stub
        import tendermint_tpu.cli  # noqa: F401
        print("DEGRADED_OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", prog], cwd=_ROOT, capture_output=True,
        text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stderr
    assert "DEGRADED_OK" in r.stdout


# ------------------------------------------------------------ analysis


def device_exposition(cells=(("ed25519_bitmap", "512", 1),),
                      h2d=1 << 20, d2h=4096, live=None, high=None,
                      planes=()):
    """Render tendermint_device_* series through the real registry."""
    reg = Registry()
    m = DeviceMetrics(reg)
    for fn, rows, count in cells:
        m.compiles.add(count, fn)
        m.bucket_compiles.add(count, fn, rows)
        for _ in range(count):
            m.compile_seconds.observe(2.0)
    m.transfer_bytes.add(h2d, "h2d")
    m.transfer_bytes.add(d2h, "d2h")
    m.transfers.add(3, "h2d")
    m.transfers.add(3, "d2h")
    if live is not None:
        m.live_buffer_bytes.set(live)
        m.live_buffer_high_water.set(high if high is not None else live)
    for plane, nbytes, entries in planes:
        m.cache_resident_bytes.set(nbytes, plane)
        m.cache_resident_entries.set(entries, plane)
    return reg.gather()


def test_device_digest_roundtrip():
    exp = parse_exposition(device_exposition(
        cells=(("ed25519_bitmap", "512", 1), ("rlc", "1024", 3)),
        live=5 << 20, high=6 << 20,
        planes=(("ed25519_pk", 2048, 2),),
    ))
    d = device_digest(exp)
    assert d["compiles"] == 4
    assert d["compiles_by_fn"] == {"ed25519_bitmap": 1, "rlc": 3}
    assert {"fn": "rlc", "rows": "1024", "count": 3} in d["bucket_compiles"]
    assert d["compile_seconds_total"] == pytest.approx(8.0)
    assert d["transfer_bytes"] == {"h2d": 1 << 20, "d2h": 4096}
    assert d["live_buffer_bytes"] == 5 << 20
    assert d["high_water_bytes"] == 6 << 20
    assert d["cache_planes"] == {"ed25519_pk": {"bytes": 2048, "entries": 2}}
    # devobs-off scrape -> no digest at all (absence is not evidence)
    from tendermint_tpu.metrics import ConsensusMetrics

    reg = Registry()
    ConsensusMetrics(reg)
    assert device_digest(parse_exposition(reg.gather())) is None


def test_recompile_offenders_trip_condition():
    clean = {"bucket_compiles": [{"fn": "a", "rows": "512", "count": 1}]}
    churn = {"bucket_compiles": [{"fn": "a", "rows": "512", "count": 4},
                                 {"fn": "b", "rows": "64", "count": 1}]}
    assert recompile_offenders([("n1", clean)]) == []
    assert recompile_offenders([("n1", clean), ("n2", churn)]) == [
        ("n2", "a", "512", 4)
    ]
    # slack loosens the same condition, not a second copy of it
    assert recompile_offenders([("n2", churn)], slack=3) == []
    assert recompile_offenders([("n3", None)]) == []


def test_mem_growth_offenders_trip_condition():
    mono = [(float(i), float((1 << 20) * (i + 1))) for i in range(8)]
    assert mem_growth_offenders([("n1", mono)]) == [("n1", 7 << 20, 8)]
    # one dip in the tail breaks monotonicity -> not a leak signature
    dipped = list(mono)
    dipped[5] = (5.0, 0.0)
    assert mem_growth_offenders([("n1", dipped)]) == []
    # growth under the floor never trips
    flat = [(float(i), 100.0 + i) for i in range(8)]
    assert mem_growth_offenders([("n1", flat)]) == []
    # fewer than tail_points samples cannot prove a leak (vacuous)
    assert mem_growth_offenders([("n1", mono[:4])]) == []
    assert mem_growth_offenders([("n1", mono[:4])], tail_points=4) != []


# ------------------------------------------------- gates through analyze_run


def _write_node(run, name, metrics_text=None, timeseries=None):
    d = run / name
    d.mkdir(parents=True, exist_ok=True)
    if metrics_text is not None:
        (d / "metrics.txt").write_text(metrics_text)
    if timeseries is not None:
        (d / "timeseries.jsonl").write_text(
            "\n".join(json.dumps(r) for r in timeseries) + "\n")
    return d


def _residency_records(values, t0=1000.0):
    """The flight-recorder stream shape (metrics/flight.py): a full
    anchor first, then changed-gauge ticks."""
    recs = [{"t": t0, "c": {}, "g": {LIVE_BUFFER_SERIES: values[0]}}]
    for i, v in enumerate(values[1:], 1):
        recs.append({"t": t0 + i, "g": {LIVE_BUFFER_SERIES: v}})
    return recs


def test_recompile_storm_gate_names_node_and_fn(tmp_path):
    run = tmp_path / "net"
    _write_node(run, "validator01", device_exposition())
    _write_node(run, "validator02", device_exposition(
        cells=(("sr25519_bitmap", "256", 5),)))
    report = analyze_run(str(run))
    (gate,) = [g for g in report["gates"] if g["name"] == "recompile_storm"]
    assert not gate["ok"]
    assert "validator02" in gate["detail"] and "sr25519_bitmap" in gate["detail"]
    # node digests carried the evidence the gate judged
    n2 = next(s for s in report["nodes"] if s["name"] == "validator02")
    assert n2["device"]["compiles_by_fn"]["sr25519_bitmap"] == 5
    # slack override passes the same evidence
    loose = analyze_run(str(run), gates={"recompile_slack": 4})
    (gate,) = [g for g in loose["gates"] if g["name"] == "recompile_storm"]
    assert gate["ok"]


def test_device_gates_pass_vacuously_without_device_series(tmp_path):
    run = tmp_path / "net"
    from tendermint_tpu.metrics import ConsensusMetrics

    reg = Registry()
    ConsensusMetrics(reg)
    _write_node(run, "validator01", reg.gather())
    report = analyze_run(str(run))
    for name in ("recompile_storm", "device_mem_growth"):
        (gate,) = [g for g in report["gates"] if g["name"] == name]
        assert gate["ok"] and "tmdev off" in gate["detail"], gate


def test_device_mem_growth_gate_trips_on_monotone_tail(tmp_path):
    run = tmp_path / "net"
    leak = [float((1 << 20) * (i + 1)) for i in range(10)]
    _write_node(run, "validator01", device_exposition(),
                timeseries=_residency_records(leak))
    healthy = [float(1 << 20)] * 6 + [float(1 << 19)] + [float(1 << 20)] * 5
    _write_node(run, "validator02", device_exposition(),
                timeseries=_residency_records(healthy))
    report = analyze_run(str(run))
    (gate,) = [g for g in report["gates"] if g["name"] == "device_mem_growth"]
    assert not gate["ok"]
    assert "validator01" in gate["detail"]
    assert "validator02" not in gate["detail"]
    # per-node device_memory block persisted the judged tail
    n1 = next(s for s in report["nodes"] if s["name"] == "validator01")
    assert n1["device_memory"]["last_bytes"] == 10 << 20
    assert len(n1["device_memory"]["tail"]) == 10
    # a raised floor passes the same evidence
    loose = analyze_run(
        str(run), gates={"device_mem_growth_min_bytes": 1 << 30})
    (gate,) = [g for g in loose["gates"] if g["name"] == "device_mem_growth"]
    assert gate["ok"]


def test_unknown_device_gate_key_raises(tmp_path):
    run = tmp_path / "net"
    _write_node(run, "validator01", device_exposition())
    with pytest.raises(ValueError, match="recompile_slak"):
        analyze_run(str(run), gates={"recompile_slak": 1})
