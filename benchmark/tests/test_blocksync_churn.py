"""`blocksync-1k-churn` at the rehearsal size (16 validators, 48 blocks,
one rotation a block; its own `chain-tiny-1k-churn`, written into the
rehearsal's root as a new cell's files are), the readers it brought,
and its upper control: control flow and arithmetic only."""

import json
import os
import shutil

import pytest

from benchmark.tools import faults, faults_sync_churn
from conftest import DATA, run_cell

CELL = "blocksync-1k-churn"
NEW = ("state_save_ms_per_block.sync", "valset_update_ms_per_block.sync",
       "pk_fill_ms_per_block.sync", "pk_fill_rows_per_key.sync")


def tiny_config() -> dict:
    with open(os.path.join(DATA, "benchmark", "configs", "chain-tiny-1k-churn.json")) as f:
        return json.load(f)


@pytest.fixture(autouse=True)
def rehearsal_settings(monkeypatch):
    """The rehearsal's settings, whichever of the session's tests
    imported the program first: the 11-row light batch and the 16-row
    full one on the cached per-signature route, where the fills are."""
    import tendermint_tpu.crypto.ed25519 as ed

    for key, value in tiny_config()["env"].items():
        monkeypatch.setenv(key, str(value))
    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", int(tiny_config()["env"]["TM_TPU_BATCH_CUTOVER"]))
    monkeypatch.setattr(ed, "MSM_BATCH_CUTOVER", int(tiny_config()["env"]["TM_TPU_MSM_CUTOVER"]))


@pytest.fixture
def churn_root(tiny_root):
    """The rehearsal's root with the cell pointed at its own tiny
    configuration (`make_root` points every cell at `chain-tiny`, which
    has no `rotation`)."""
    name = "chain-tiny-1k-churn.json"
    shutil.copy(os.path.join(DATA, "benchmark", "configs", name),
                os.path.join(tiny_root, "benchmark", "configs", name))
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "chain-tiny-1k-churn", "source": "a rehearsal",
                             "file": "benchmark/configs/" + name,
                             "reduced": ["blocks", "validators"], "why": "rehearsal"})
    next(w for w in bench["workloads"] if w["name"] == CELL)["config"] = "chain-tiny-1k-churn"
    with open(path, "w") as f:
        json.dump(bench, f)
    return tiny_root


def test_the_cell_is_correct_and_reports_its_end_to_end_metrics(churn_root, capsys):
    code, result = run_cell(churn_root, CELL, capsys=capsys)
    assert code == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"sync_rate", "setup_s"}
    assert {name: c["value"] for name, c in result["compared"].items()} == dict.fromkeys(
        ["blocks_differing_from_source", "headers_differing_from_reference_hash",
         "applied_commits_the_reference_refuses", "app_hash_or_height_wrong",
         "validator_sets_differing_from_schedule", "passes_halted_or_blaming_an_honest_peer",
         "refusal_faults", "programs_compiled_in_the_window", "windows_with_no_operation"], 0)


def test_a_traced_run_reports_every_new_metric(churn_root, capsys):
    """A fill a block, each at the launch bucket of the batch that met
    the new key: the 11-row light batch or the 16-row full one (16
    rows), or the two coalesced (32). A seed of its own: the process's
    pubkey cache holds every key of the chains other tests built."""
    code, result = run_cell(churn_root, CELL, seed=7, seconds=3.0, trace=1, capsys=capsys)
    assert code == 0 and result["correct"] is True
    values = {name: m["value"] for name, m in result["metrics"].items()}
    assert set(NEW) <= set(values)
    assert values["state_save_ms_per_block.sync"] > 0.0
    assert values["valset_update_ms_per_block.sync"] > 0.0
    assert values["pk_fill_ms_per_block.sync"] > 0.0
    assert 16.0 <= values["pk_fill_rows_per_key.sync"] <= 32.0
    assert values["verify_ahead_stale_share.sync"] == 0.0
    assert {"apply_ms_per_block.sync", "compiles_in_window", "programs_loaded"} <= set(values)
    assert "rlc_scalars_ms_per_launch.sync" not in values


@pytest.mark.parametrize("fault,numbers", [
    ("changes_at_once", ("validator_sets_differing_from_schedule",
                         "applied_commits_the_reference_refuses")),
    ("half_batch", ("refusal_faults",)),
])
def test_correct_comes_out_false_under_the_control_and_with_half_a_batch_left_out(
        churn_root, capsys, fault, numbers):
    make = dict(faults.FAULTS, changes_at_once=faults_sync_churn.changes_at_once)[fault]
    undo = []
    try:
        code, result = run_cell(churn_root, CELL, capsys=capsys,
                                before_window=lambda: undo.append(make()))
    finally:
        for u in undo:
            u()
    assert code == 0 and result["correct"] is False
    assert sum(result["compared"][n]["value"] for n in numbers) >= 1
