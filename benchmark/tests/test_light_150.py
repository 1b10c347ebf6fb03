"""`light-150-skip` at the rehearsal size, and the readers it brought,
each on a hand-made slice: control flow and arithmetic only."""

import importlib
import json
import os

import pytest

from conftest import ROOT, run_cell

CELL = "light-150-skip"
ENGINE = "tendermint_engine_"


def test_the_cell_is_correct_and_reports_its_end_to_end_metrics(tiny_root, capsys):
    code, result = run_cell(tiny_root, CELL, capsys=capsys)
    assert code == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"light_rate", "light_update_p95", "setup_s"}


def test_a_traced_run_reports_every_new_program_side_metric(tiny_root, capsys):
    """chain-tiny's 9- and 17-signature batches lie about its cutovers
    (6 and 16) as chain-150's 51 and 101 lie about 8 and 64: cached
    bitmap, then the MSM. XLA:CPU has no device plane and no probe: the
    device's readers and the three prices say nothing."""
    code, result = run_cell(tiny_root, CELL, seconds=3.0, trace=1, capsys=capsys)
    assert code == 0 and result["correct"] is True
    values = {name: m["value"] for name, m in result["metrics"].items()}
    assert values["verify_ms_per_update.light"] > 0
    assert values["engine_device_rows_share.light"] == 100.0
    # 9 of an update's 26 rows, less a walk's trust root (17 rows, MSM) every four updates
    assert 10.0 < values["engine_bitmap_rows_share.light"] < 40.0
    assert values["engine_msm_cutover.light"] == 16.0
    assert values["pk_cache_hit_share.light"] == 100.0  # the warm-up walk filled the cache
    assert not set(values) & {"bitmap_kernel_ms_per_launch.light", "bitmap_roofline.light",
                              "autotune_host_us_per_sig", "autotune_host_route_us_per_sig",
                              "autotune_launch_ms"}


def read(metric: str, ctx: dict):
    return importlib.import_module("benchmark.metrics." + metric).read(ctx)


def make_ctx(rows=None, launches=None, ops=(), gauges=None, cache=(0, 0), spans=()):
    """A slice in which `rows`/`launches` ({path: n}) were verified,
    the device ran `ops` ([name, seconds]), the engine's gauges read
    `gauges` and the pubkey cache looked up and missed `cache` rows."""
    after = {}
    for path, n in (rows or {}).items():
        after[(ENGINE + "path_rows_total",
               (("path", path), ("plane", "ed25519"), ("status", "accept")))] = float(n)
    for path, n in (launches or {}).items():
        after[(ENGINE + "launches_total", (("path", path), ("plane", "ed25519")))] = float(n)
    for series, n in zip(("pk_cache_rows_total", "pk_cache_missed_rows_total"), cache):
        if n is not None:
            after[(ENGINE + series, (("plane", "ed25519_pk"),))] = float(n)
    for series, value in (gauges or {}).items():
        after[(ENGINE + series, ())] = value
    with open(os.path.join(ROOT, "benchmark", "work.json")) as f:
        work = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    return {"spans": list(spans), "counters": {"before": {}, "after": after},
            "device": {"ops": [list(op) for op in ops]} if ops else None,
            "cutovers": {"device": 8, "msm": 64}, "work": work, "peaks": peaks}


BOTH_ROUTES = dict(rows={"bitmap": 510, "two_phase_msm": 1010},
                   launches={"bitmap": 10, "two_phase_msm": 10},
                   ops=[["jit_msm_verify_kernel_impl", 0.120],
                        ["jit_verify_kernel_cached_split_impl", 0.070],
                        ["jit_build_pk_tables_split_impl", 0.010],
                        ["jit_bench_anchor", 1e-6]])


@pytest.mark.parametrize("metric,want", [
    ("engine_bitmap_rows_share", 100.0 * 510 / 1520),
    ("engine_msm_cutover", 64.0),
    ("bitmap_kernel_ms_per_launch", 8.0),  # 80 ms of the two per-signature programs, 10 launches
    ("bitmap_roofline", 100.0 * 510 * 309024 * 2 / (0.080 * 393e12)),
])
def test_the_route_readers_split_the_bitmap_route_from_the_msm(metric, want):
    assert read(metric, make_ctx(**BOTH_ROUTES)) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["bitmap_kernel_ms_per_launch", "bitmap_roofline",
                                    "pk_cache_hit_share"])
@pytest.mark.parametrize("ctx", [
    make_ctx(rows={"host": 1520}, launches={"host": 20}),  # every batch under the cutover
    make_ctx(rows={"two_phase_msm": 1001}, launches={"two_phase_msm": 2},
             ops=[["jit_msm_verify_kernel_impl", 0.028]], cache=(None, None)),  # the parent, MSM only
], ids=["host", "msm_only"])
def test_a_reader_whose_route_did_not_run_says_nothing(metric, ctx):
    assert read(metric, ctx) is None


def test_the_bitmap_share_of_rows_is_a_reading_at_zero_and_nothing_without_rows():
    assert read("engine_bitmap_rows_share", make_ctx(rows={"host": 1520})) == 0.0
    assert read("engine_bitmap_rows_share", make_ctx()) is None


def test_the_cache_hit_share_counts_missed_rows_against_rows_looked_up():
    assert read("pk_cache_hit_share", make_ctx(cache=(510, 0))) == 100.0
    assert read("pk_cache_hit_share", make_ctx(cache=(200, 50))) == 75.0


def test_the_prices_are_read_from_the_gauges_and_absent_without_them():
    ctx = make_ctx(gauges={"autotune_host_sig_seconds": 1.2e-3,
                           "autotune_host_route_sig_seconds": 30e-6,
                           "autotune_launch_seconds": 9.5e-3})
    assert read("autotune_host_us_per_sig", ctx) == pytest.approx(1200.0)
    assert read("autotune_host_route_us_per_sig", ctx) == pytest.approx(30.0)
    assert read("autotune_launch_ms", ctx) == pytest.approx(9.5)
    for metric in ("autotune_host_us_per_sig", "autotune_host_route_us_per_sig",
                   "autotune_launch_ms"):
        assert read(metric, make_ctx()) is None


def test_verification_per_update_sums_submission_and_wait_over_updates_ended():
    def span(name, t0, t1, ends=True):
        return {"name": name, "cat": "x", "t0": t0 * 1e6, "t1": t1 * 1e6, "tid": 1,
                "ends_in_slice": ends, "args": {}}

    spans = [span("light.update", 0, 30), span("verify.commit_dispatch", 1, 2),
             span("verify.commit_collect", 2, 12), span("verify.commit_dispatch", 13, 14),
             span("verify.commit_collect", 14, 28), span("light.update", 30, 40, ends=False)]
    assert read("verify_ms_per_update", make_ctx(spans=spans)) == pytest.approx(26.0)
    assert read("verify_ms_per_update", make_ctx()) is None


def test_the_configuration_has_every_key_of_chain_1k_and_a_short_source():
    with open(os.path.join(ROOT, "benchmark", "configs", "chain-1k.json")) as f:
        model = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "configs", "chain-150.json")) as f:
        config = json.load(f)
    assert set(model) <= set(config) and "env" not in config
    assert len(config["source"]) <= 200 and config["validators"] == 150
    assert config["fixes"] == model["fixes"] and config["guarantees"] == model["guarantees"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "chain-150")
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("chain-150", "light-skip", 1)
