"""`light-150-churn` at the rehearsal size (24 validators, 80 blocks,
one rotation a block; its own `chain-tiny-churn` and mix, written into
the rehearsal's root as a new cell's files are), its control, and the
readers it brought, each on a hand-made slice: control flow and
arithmetic only."""

import importlib
import json
import os
import shutil

import pytest

from benchmark.tools import faults, faults_churn
from conftest import DATA, ROOT, run_cell

CELL = "light-150-churn"
NEW = ("steps_per_update.light", "refused_jumps_per_update.light", "bisect_ms_per_update.light")


@pytest.fixture
def churn_root(tiny_root):
    """The rehearsal's root with the cell pointed at its own tiny
    configuration (`make_root` points every cell at `chain-tiny`, which
    has no `rotation`) and its own spans."""
    for sub, name in (("configs", "chain-tiny-churn.json"), ("traffic", "light-catchup-tiny.json")):
        shutil.copy(os.path.join(DATA, "benchmark", sub, name),
                    os.path.join(tiny_root, "benchmark", sub, name))
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "chain-tiny-churn", "source": "a rehearsal",
                             "file": "benchmark/configs/chain-tiny-churn.json",
                             "reduced": ["blocks", "validators"], "why": "rehearsal"})
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    cell["config"], cell["traffic"] = "chain-tiny-churn", "light-catchup-tiny"
    with open(path, "w") as f:
        json.dump(bench, f)
    return tiny_root


@pytest.fixture
def host_route(monkeypatch):
    """Every batch through the host's C loop: a walk takes a fifth of a
    second here, so a window holds many and a slice some. The device
    routes run the same walk in tests/test_light_churn.py."""
    import tendermint_tpu.crypto.ed25519 as ed

    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", 100)
    monkeypatch.setattr(ed, "MSM_BATCH_CUTOVER", 100)


def test_the_cell_is_correct_on_the_device_routes_and_reports_its_end_to_end_metrics(
        churn_root, capsys):
    code, result = run_cell(churn_root, CELL, capsys=capsys)
    assert code == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"light_rate", "light_update_p95", "setup_s"}
    assert set(result["compared"]) >= {"validator_sets_differing_from_schedule",
                                       "trust_links_the_reference_refuses", "refusal_faults"}
    assert "stored_commits_the_reference_refuses" not in result["compared"]


def test_a_traced_run_reports_the_three_new_readers(churn_root, host_route, capsys):
    """The tiny walk is spans 4, 9, 20, 42, 4: nine steps, four refused
    jumps and four pivots in five updates. The slice holds some of them."""
    code, result = run_cell(churn_root, CELL, seconds=4.0, trace=1, capsys=capsys)
    assert code == 0 and result["correct"] is True
    values = {name: m["value"] for name, m in result["metrics"].items()}
    assert set(NEW) <= set(values)
    assert 1.0 <= values["steps_per_update.light"] <= 4.0
    assert 0.0 < values["refused_jumps_per_update.light"] <= 3.0
    assert values["bisect_ms_per_update.light"] > 0.0
    assert values["steps_per_update.light"] > values["refused_jumps_per_update.light"]
    assert {"fetch_ms_per_update.light", "verify_ms_per_update.light",
            "engine_device_rows_share.light"} <= set(values)
    assert not set(values) & {"rlc_scalars_ms_per_launch.light", "engine_msm_cutover.light"}


@pytest.mark.parametrize("fault,number,at_least", [
    ("trusting_passes", "trust_links_the_reference_refuses", 1),
    ("half_batch", "refusal_faults", 2),
])
def test_correct_comes_out_false_under_the_control_and_with_half_a_batch_left_out(
        churn_root, host_route, capsys, fault, number, at_least):
    make = dict(faults.FAULTS, trusting_passes=faults_churn.trusting_passes)[fault]
    undo = []
    try:
        code, result = run_cell(churn_root, CELL, capsys=capsys,
                                before_window=lambda: undo.append(make()))
    finally:
        for u in undo:
            u()
    assert code == 0 and result["correct"] is False
    assert result["compared"][number]["value"] >= at_least


# ------------------------------------------------- the readers on a hand-made slice


def read(metric: str, spans: list) -> float | None:
    return importlib.import_module("benchmark.metrics." + metric).read({"spans": spans})


def span(name, t0, t1, ends=True, **args):
    return {"name": name, "cat": "light", "t0": t0 * 1e6, "t1": t1 * 1e6, "tid": 1,
            "ends_in_slice": ends, "args": args}


def a_bisecting_slice(purpose: bool = True) -> list:
    """A trust root, a direct update and a bisecting one (one refused
    jump of 1 ms, a pivot fetched in 2 ms, two steps), and an update
    still open when the slice ends."""
    what = (lambda p: {"purpose": p}) if purpose else (lambda p: {})
    return [
        span("light.update", 0, 8, mode="root"), span("light.fetch", 0, 2, **what("target")),
        span("light.update", 8, 20, mode="skipping"), span("light.fetch", 8, 10, **what("target")),
        span("light.verify_step", 10, 18, outcome="ok"),
        span("light.fetch", 18, 20, **what("witness")),
        span("light.update", 20, 50, mode="skipping"), span("light.fetch", 20, 22, **what("target")),
        span("light.verify_step", 22, 23, outcome="bisect"),
        span("light.fetch", 23, 25, **what("pivot")),
        span("light.verify_step", 25, 35, outcome="ok"),
        span("light.verify_step", 35, 45, outcome="ok"),
        span("light.fetch", 45, 47, **what("witness")),
        span("light.update", 50, 60, ends=False, mode="skipping"),
        span("light.verify_step", 52, 53, outcome="bisect"),
        span("light.fetch", 53, 55, **what("pivot")),
    ]


@pytest.mark.parametrize("metric,want", [
    ("steps_per_update", 3 / 2),  # three steps that succeeded, two updates ended, the root left out
    ("refused_jumps_per_update", 2 / 2),
    ("bisect_ms_per_update", (1 + 2 + 1 + 2) / 2),  # the open update's refusal and pivot count too
])
def test_the_new_readers_count_steps_refusals_and_bisection_time_per_update(metric, want):
    assert read(metric, a_bisecting_slice()) == pytest.approx(want)


@pytest.mark.parametrize("metric,spans", [
    ("steps_per_update", []),
    ("refused_jumps_per_update", [span("light.update", 0, 8, mode="root")]),
    ("bisect_ms_per_update", []),
    # the parent's program: `light.fetch` says no purpose, so a pivot cannot be told from a target
    ("bisect_ms_per_update", a_bisecting_slice(purpose=False)),
    # a program whose steps say no outcome
    ("steps_per_update", [span("light.update", 0, 9, mode="skipping"),
                          span("light.verify_step", 1, 8)]),
], ids=["no_span", "a_root_only", "no_span_ms", "no_purpose", "no_outcome"])
def test_a_new_reader_with_nothing_to_read_says_nothing(metric, spans):
    assert read(metric, spans) is None


def test_the_parents_program_still_gives_steps_and_refusals():
    """`outcome` was on `light.verify_step` before this PR; `purpose` was not."""
    spans = a_bisecting_slice(purpose=False)
    assert read("steps_per_update", spans) == pytest.approx(1.5)
    assert read("refused_jumps_per_update", spans) == pytest.approx(1.0)


def test_the_configuration_keeps_every_shape_of_chain_150_and_states_its_rotation():
    with open(os.path.join(ROOT, "benchmark", "configs", "chain-150.json")) as f:
        model = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "configs", "chain-150-churn.json")) as f:
        config = json.load(f)
    assert set(model) <= set(config) and "env" not in config
    for key in ("validators", "voting_power", "key_type", "txs_per_block", "blocks", "reduced"):
        assert config[key] == model[key], key
    assert config["fixes"] == [f for f in model["fixes"] if f != "no validator-set change"]
    assert config["guarantees"][: len(model["guarantees"])] == model["guarantees"]
    assert len(config["guarantees"]) == len(model["guarantees"]) + 2
    assert config["rotation"] == {"validators_per_block": 1, "leaves": "longest-serving",
                                  "joins": "a key never seen"}
    assert len(config["source"]) <= 200 and {"generator", "validators"} <= set(config["assumed"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "chain-150-churn")
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("chain-150-churn", "light-catchup", 1)
    with open(os.path.join(ROOT, "benchmark", "traffic", "light-catchup.json")) as f:
        mix = json.load(f)
    assert mix["driver"] == "light_catchup" and mix["spans"] == [20, 45, 110, 230]
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["layer"] == "caller"
