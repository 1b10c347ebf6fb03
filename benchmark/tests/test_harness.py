"""The harness end to end at the rehearsal size, the look for a chip
skipped: a run's control flow, its result line, and `correct` coming
out false under the control and under every fault a cell can have."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.tools import faults
from conftest import ROOT, run_cell

E2E = {"blocksync-1k": {"sync_rate", "setup_s"},
       "blocksync-4": {"sync_rate", "sync_rate_bypass", "setup_s"},
       "light-1k-skip": {"light_rate", "light_update_p95", "setup_s"}}


@pytest.mark.parametrize("workload", sorted(E2E))
def test_a_run_is_correct_and_reports_its_end_to_end_metrics(tiny_root, capsys, workload):
    code, result = run_cell(tiny_root, workload, capsys=capsys)
    assert code == 0
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 2
    assert set(result["metrics"]) == E2E[workload]
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "compared" and all(
        c["value"] <= c["limit"] for c in result["compared"].values())


def test_a_traced_run_reports_per_layer_metrics_and_no_device_number(tiny_root, capsys):
    code, result = run_cell(tiny_root, "light-1k-skip", seconds=3.0, trace=1, capsys=capsys)
    assert code == 0 and result["correct"] is True
    got = set(result["metrics"])
    assert {"host_ms_per_update.light", "commit_walk_ms.light", "engine_device_rows_share.light",
            "update_p50.light", "compiles_in_window"} <= got
    # XLA:CPU has no device plane: the device's readers find nothing and say nothing
    assert not got & {"device_idle_share.light", "kernel_ms_per_launch.light",
                      "verify_roofline.light"}
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert result["metrics"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("workload", ["blocksync-1k", "light-1k-skip"])
@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_correct_comes_out_false_with_the_timed_path_broken(tiny_root, capsys, workload, fault):
    undo = []
    try:
        code, result = run_cell(tiny_root, workload, capsys=capsys,
                                before_window=lambda: undo.append(faults.FAULTS[fault]()))
    finally:
        for u in undo:
            u()
    assert code == 0 and result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["compared"].values())


def test_the_command_fails_without_a_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-m", "benchmark.run", "--workload", "blocksync-4",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3 and p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_the_command_fails_in_a_directory_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "-m", "benchmark.run", "--workload", "blocksync-4",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_new_cells_are_found_by_name_with_no_edit(tiny_root, tmp_path, capsys):
    """A configuration, a traffic mix, a per-layer metric and a cell,
    each a new file or a new entry."""
    import benchmark.metrics

    bench_path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    with open(os.path.join(tiny_root, "benchmark", "configs", "chain-tiny.json")) as f:
        config = dict(json.load(f), name="chain-new", validators=16, chain_id="chain-new")
    with open(os.path.join(tiny_root, "benchmark", "configs", "chain-new.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(tiny_root, "benchmark", "traffic", "light-skip.json")) as f:
        mix = dict(json.load(f), skips=[2, 5], witnesses=2)
    with open(os.path.join(tiny_root, "benchmark", "traffic", "light-two-witnesses.json"), "w") as f:
        json.dump(mix, f)
    readers = tmp_path / "readers"
    readers.mkdir()
    (readers / "walks_in_window.py").write_text(
        "def read(ctx):\n    return float(ctx['window']['walks'])\n")
    benchmark.metrics.__path__.append(str(readers))
    try:
        bench["configs"].append({"name": "chain-new", "source": "a rehearsal",
                                 "file": "benchmark/configs/chain-new.json", "reduced": [],
                                 "why": "rehearsal"})
        bench["workloads"].append({"name": "new-cell", "config": "chain-new",
                                   "traffic": "light-two-witnesses", "chips": 1, "why": "rehearsal"})
        for m in bench["end_to_end"]:
            if m["name"] in ("light_rate", "light_update_p95"):
                m["workloads"].append("new-cell")
        bench["per_layer"].append({"name": "walks_in_window.light", "unit": "count",
                                   "better": "higher", "source": "host_clock", "layer": "caller",
                                   "moves": "light_rate", "workloads": ["new-cell"]})
        with open(bench_path, "w") as f:
            json.dump(bench, f)
        code, result = run_cell(tiny_root, "new-cell", seconds=2.0, trace=1, capsys=capsys)
    finally:
        benchmark.metrics.__path__.remove(str(readers))
    assert code == 0 and result["correct"] is True
    assert result["metrics"]["walks_in_window.light"]["value"] >= 1
    assert "compiles_in_window" in result["metrics"]  # a metric with no `workloads` key
