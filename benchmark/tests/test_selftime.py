"""Self time and the readers that came with the program's span
catalogue: the arithmetic on hand-made spans with known answers, then a
traced rehearsal of the two cells that launch, in which every new
metric reports a number (none of them needs the device's plane)."""

import importlib
import json
import os

import pytest

from benchmark.selftime import self_ms_per_op, self_time_ns
from conftest import ROOT, run_cell

MS = 1e6  # ns


def span(name, t0_ms, t1_ms, tid=1, ended=True, **args):
    return {"name": name, "cat": name.split(".")[0], "tid": tid, "t0": t0_ms * MS,
            "t1": t1_ms * MS, "ends_in_slice": ended, "args": args}


def read(metric, ctx):
    return importlib.import_module("benchmark.metrics." + metric).read(ctx)


def make_ctx(spans, launches=0.0, slice_s=None):
    key = ("tendermint_engine_launches_total", (("path", "two_phase_msm"), ("plane", "ed25519")))
    host = ("tendermint_engine_launches_total", (("path", "host"), ("plane", "ed25519")))
    return {"spans": spans, "device": None if slice_s is None else {"window_s": slice_s},
            "counters": {"before": {key: 10.0, host: 5.0},
                         "after": {key: 10.0 + launches, host: 9.0}}}


# ----------------------------------------------------------- self time


def test_self_time_by_parent_ids():
    root = span("root", 0, 100, span=1, parent=0, req=1)
    spans = [
        root,
        span("a", 10, 30, span=2, parent=1, req=1),
        span("a.inner", 12, 20, span=3, parent=2, req=1),  # a grandchild: a covers it
        span("b", 25, 50, span=4, parent=1, req=1),  # overlaps a by 5 ms
        span("worker", 0, 100, tid=2, span=5, parent=1, req=1),  # another thread's: beside it
        span("stranger", 60, 70, span=6, parent=9, req=9),  # same thread, not its child
        span("late", 90, 120, span=7, parent=1, req=1),  # runs past the root: clipped
    ]
    assert self_time_ns(root, spans) == pytest.approx((100 - 40 - 10) * MS)
    assert self_time_ns(spans[1], spans) == pytest.approx(12 * MS)
    assert self_time_ns(spans[2], spans) == pytest.approx(8 * MS)  # a leaf is all self


def test_self_time_by_containment_where_there_are_no_ids():
    root = span("root", 0, 100)
    spans = [
        root,
        span("a", 10, 30),
        span("a.inner", 12, 20),
        span("b", 40, 50),
        span("worker", 5, 95, tid=2),
        span("around", 0, 200),  # contains the root: not a child
        span("twin", 0, 100),  # the same interval: not a child either
        span("astride", 95, 105),  # not inside
    ]
    assert self_time_ns(root, spans) == pytest.approx(70 * MS)
    assert self_time_ns(spans[1], spans) == pytest.approx(12 * MS)


def test_self_ms_per_op_counts_the_spans_that_ended_in_the_slice():
    spans = [
        span("op", 0, 10, span=1, parent=0, applied=True),
        span("work", 0, 8, span=2, parent=1),
        span("op", 10, 30, span=3, parent=0, applied=True),
        span("work", 12, 30, span=4, parent=3),
        span("op", 30, 31, span=5, parent=0, applied=False),
        span("op", 31, 40, ended=False, span=6, parent=0, applied=True),  # cut by the slice's end
        span("work", 31, 39, span=7, parent=6),
    ]
    ctx = {"spans": spans}
    assert self_ms_per_op(ctx, "op", lambda sp: sp["args"]["applied"]) == pytest.approx(5 / 2)
    assert self_ms_per_op(ctx, "op") == pytest.approx(6 / 3)
    assert self_ms_per_op(ctx, "absent") is None


# ------------------------------------------------------------- readers


def light_update(t0, span_id, ended=True):
    """One 100 ms update from t0: two fetches (30 ms), a step whose two
    commit checks walk for 4 and 6 ms, the witness check, the store."""
    s = span_id
    return [
        span("light.update", t0, t0 + 100, ended=ended, span=s, parent=0, req=s),
        span("light.fetch", t0 + 1, t0 + 21, ended=ended, span=s + 1, parent=s, req=s),
        span("light.verify_step", t0 + 25, t0 + 75, ended=ended, span=s + 2, parent=s, req=s),
        span("verify.commit_walk", t0 + 30, t0 + 34, ended=ended, span=s + 3, parent=s + 2, req=s),
        span("verify.commit_walk", t0 + 50, t0 + 56, ended=ended, span=s + 4, parent=s + 2, req=s),
        span("light.detect_divergence", t0 + 75, t0 + 90, ended=ended, span=s + 5, parent=s,
             req=s),
        span("light.fetch", t0 + 76, t0 + 86, ended=ended, span=s + 6, parent=s + 5, req=s),
        span("light.store", t0 + 90, t0 + 93, ended=ended, span=s + 7, parent=s, req=s),
    ]


def test_the_light_cells_readers():
    ctx = make_ctx(light_update(0, 10) + light_update(100, 20))
    assert read("fetch_ms_per_update", ctx) == pytest.approx(30.0)
    # 100 - (20 + 50 + 15 + 3): the fetch under detect_divergence is its child, not the root's
    assert read("unattributed_ms_per_op", ctx) == pytest.approx(12.0)
    assert read("sign_bytes_ms_per_commit", ctx) == pytest.approx(5.0)
    assert read("pool_starved_share", ctx) is None  # no joiner here
    # an update the slice's end cut adds its time and is not counted, as span_ms has it
    cut = make_ctx(light_update(0, 10) + [span("light.update", 100, 120, ended=False, span=30,
                                               parent=0, req=30)])
    assert read("unattributed_ms_per_op", cut) == pytest.approx(12.0 + 20.0)
    assert read("fetch_ms_per_update", make_ctx([])) is None


def block(t0, span_id, height):
    s = span_id
    return [
        span("blocksync.try_sync", t0, t0 + 200, span=s, parent=0, req=s, height=height,
             applied=True),
        span("verify.commit_collect", t0 + 2, t0 + 4, span=s + 1, parent=s, req=s),
        span("blocksync.verify_ahead", t0 + 4, t0 + 20, span=s + 2, parent=s, req=s),
        span("verify.commit_walk", t0 + 8, t0 + 16, span=s + 3, parent=s + 2, req=s),
        span("blocksync.save_block", t0 + 22, t0 + 30, span=s + 4, parent=s, req=s),
        span("blocksync.apply", t0 + 30, t0 + 195, span=s + 5, parent=s, req=s),
        span("verify.commit_walk", t0 + 31, t0 + 43, span=s + 6, parent=s + 5, req=s),
    ]


def test_the_blocksync_cells_readers():
    spans = block(0, 10, 7) + block(300, 20, 8) + [
        span("blocksync.try_sync", 200, 200.01, span=30, parent=0, req=30, applied=False),
        span("blocksync.starved", 200, 300, span=31, parent=0, req=31, polls=9),
    ]
    ctx = make_ctx(spans, slice_s=1.0)
    # 200 - (2 + 16 + 8 + 165) = 9 ms a block; the poll that applied nothing is left out
    assert read("unattributed_ms_per_op", ctx) == pytest.approx(9.0)
    assert read("sign_bytes_ms_per_commit", ctx) == pytest.approx(10.0)
    assert read("pool_starved_share", ctx) == pytest.approx(10.0)
    # 0 is a reading: blocks were applied and the loop never went without
    assert read("pool_starved_share", make_ctx(block(0, 10, 7), slice_s=1.0)) == 0.0
    # a rehearsal has no device trace: the slice is the extent of its spans
    assert read("pool_starved_share", make_ctx(spans)) == pytest.approx(100 * 100 / 500)


LAUNCH_READERS = {"prep_ms_per_launch": "ops.prep", "rlc_scalars_ms_per_launch": "ops.rlc_scalars",
                  "h2d_ms_per_launch": "device.h2d", "device_wait_ms_per_launch": "device.wait",
                  "d2h_ms_per_launch": "device.d2h"}


@pytest.mark.parametrize("metric", sorted(LAUNCH_READERS))
def test_a_launch_reader_divides_its_span_by_the_device_launches(metric):
    name = LAUNCH_READERS[metric]
    spans = [span(name, 0, 6), span(name, 10, 12), span("ops.other", 0, 50)]
    assert read(metric, make_ctx(spans, launches=2.0)) == pytest.approx(4.0)
    # host-plane batches are no launches, and a span that never occurred is no reading
    assert read(metric, make_ctx(spans, launches=0.0)) is None
    assert read(metric, make_ctx([span("ops.other", 0, 50)], launches=2.0)) is None


def test_every_new_metric_is_declared_with_its_cells_and_has_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"] for c in bench["workloads"]}
    new = [m for m in bench["per_layer"]
           if m["name"].split(".")[0] in set(LAUNCH_READERS) | {
               "fetch_ms_per_update", "unattributed_ms_per_op", "pool_starved_share",
               "sign_bytes_ms_per_commit"}]
    assert len(new) == 16
    for m in new:
        assert m["source"] == "program_span" and set(m["workloads"]) <= cells
        assert callable(importlib.import_module(
            "benchmark.metrics." + m["name"].split(".")[0]).read)
        if m["name"].split(".")[0] in LAUNCH_READERS:  # blocksync-4 launches nothing
            assert "blocksync-4" not in m["workloads"]


# ----------------------------------------------------------- rehearsal


@pytest.mark.parametrize("workload", ["light-1k-skip", "blocksync-1k"])
def test_a_traced_rehearsal_reports_every_new_metric(tiny_root, capsys, monkeypatch, workload):
    from benchmark import run

    # On XLA:CPU a launch takes a third of a second and a block two of them: a slice long
    # enough that several blocks and updates end in it, on a loaded box too.
    monkeypatch.setattr(run, "SLICE_S", 4.0)
    code, result = run_cell(tiny_root, workload, seconds=12.0, trace=1, capsys=capsys)
    assert code == 0 and result["correct"] is True
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    expected = {m["name"] for m in bench["per_layer"]
                if m["source"] == "program_span" and workload in m.get("workloads", ())}
    assert len(expected) >= 10
    missing = expected - set(result["metrics"])
    assert not missing, missing
    values = {name: result["metrics"][name]["value"] for name in expected}
    assert all(v >= 0 for v in values.values()), values
    kind = "light" if workload.startswith("light") else "sync"
    # what the spans from outside could not see: the walk is not 0.1 ms of submission
    assert values[f"sign_bytes_ms_per_commit.{kind}"] > 0
    assert values[f"device_wait_ms_per_launch.{kind}"] > values[f"d2h_ms_per_launch.{kind}"]
