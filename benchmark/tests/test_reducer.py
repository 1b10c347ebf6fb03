"""The reduction from a profiler trace to busy time, kernel time and
idle gaps: on a hand-made trace with known answers, and on the slice of
a trace recorded on the chip (data/*.json, as `--keep-trace` wrote it)."""

import glob
import json
import os

import pytest

from benchmark import reducer
from conftest import DATA


def test_busy_kernel_ops_and_gaps_of_a_hand_made_trace():
    extracted = {"devices": {"/device:TPU:0": [
        ["jit_bench_anchor(77)", 0.0, 10.0],
        ["jit_msm(7)", 100.0, 200.0],       # 100-300
        ["jit_msm(9)", 250.0, 150.0],       # overlaps: 250-400
        ["jit_tables(2)", 600.0, 100.0],         # 600-700
        ["jit_msm(7)", 950.0, 100.0],       # clipped at the slice's end: 950-1000
    ]}, "marker_ns": 0.0, "lines": {}}
    spans = [{"name": "outer", "t0": 0.0, "t1": 800.0},
             {"name": "inner", "t0": 400.0, "t1": 550.0}]
    r = reducer.reduce(extracted, 0.0, 1000.0, spans)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx((10 + 300 + 100 + 50) * 1e-9)
    assert r["kernel_s"] == pytest.approx((300 + 100 + 50) * 1e-9)
    assert r["device_op_events"] == 4
    assert r["ops"][0] == ["jit_msm", pytest.approx(400e-9)]  # 200 + 150 + 50, fingerprints stripped
    gaps = dict(r["gaps"])
    # idle: 10-100 (outer), 400-600 (inner to 550, then outer), 700-950 (outer to 800)
    assert gaps["inner"] == pytest.approx(150e-9)
    assert gaps["outer"] == pytest.approx((90 + 50 + 100) * 1e-9)
    assert gaps["no_span"] == pytest.approx(150e-9)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_a_trace_with_no_device_plane_gives_nothing_not_zero():
    assert reducer.reduce({"devices": {}, "marker_ns": 0.0, "lines": {}}, 0.0, 1e9) is None


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(DATA, "*_trace.json"))))
def test_a_slice_recorded_on_the_chip(path):
    with open(path) as f:
        extracted = json.load(f)
    r = reducer.reduce(extracted, extracted["t0_ns"], extracted["t1_ns"], [])
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["kernel_s"] <= r["busy_s"]
    assert any(reducer.ANCHOR in name for name, _ in r["ops"])
    assert dict(r["gaps"])["no_span"] == pytest.approx(r["window_s"] - r["busy_s"])
    expected = extracted.get("expected")
    if expected:
        assert r["busy_s"] == pytest.approx(expected["busy_s"])
        assert r["kernel_s"] == pytest.approx(expected["kernel_s"])
