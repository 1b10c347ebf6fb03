"""`blocksync-1k-badpeer` at the rehearsal size (24 validators, 160
blocks, four serving peers of which one lies; its own
`chain-tiny-badpeer`, written into the rehearsal's root as a new cell's
files are), the schedule against the store built from it, the cell's
controls, and the readers it brought: control flow and arithmetic
only."""

import importlib
import json
import os
import shutil

import pytest

from benchmark import chain as chainlib
from benchmark import chain_badpeer
from benchmark import reference as ref
from benchmark import reference_badpeer as refbad
from benchmark.tools import faults, faults_badpeer
from conftest import DATA, run_cell

CELL = "blocksync-1k-badpeer"
NEW = ("refusals_per_100_blocks.sync", "refusal_ms_per_block.sync", "dropped_blocks_share.sync",
       "peer_out_ms.sync", "verify_ahead_stale_share.sync")


def tiny_config() -> dict:
    with open(os.path.join(DATA, "benchmark", "configs", "chain-tiny-badpeer.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module", autouse=True)
def rehearsal_settings():
    """The program reads its settings when it is first imported, and this
    file's tests may be the session's first to import it: under the
    rehearsal's, as the harness sets them for every cell of the root."""
    for key, value in tiny_config()["env"].items():
        os.environ[key] = str(value)


@pytest.fixture
def badpeer_root(tiny_root):
    """The rehearsal's root with the cell pointed at its own tiny
    configuration (`make_root` points every cell at `chain-tiny`, which
    has no `peers`)."""
    shutil.copy(os.path.join(DATA, "benchmark", "configs", "chain-tiny-badpeer.json"),
                os.path.join(tiny_root, "benchmark", "configs", "chain-tiny-badpeer.json"))
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "chain-tiny-badpeer", "source": "a rehearsal",
                             "file": "benchmark/configs/chain-tiny-badpeer.json",
                             "reduced": ["blocks", "validators"], "why": "rehearsal"})
    next(w for w in bench["workloads"] if w["name"] == CELL)["config"] = "chain-tiny-badpeer"
    with open(path, "w") as f:
        json.dump(bench, f)
    return tiny_root


@pytest.fixture
def host_route(monkeypatch):
    """Every batch through the host's C loop: a pass takes a second
    here, so a window holds several and each its refusals. On XLA:CPU
    the device routes take most of a second a block; they run the same
    loop in test_harness.py (`blocksync-1k`)."""
    import tendermint_tpu.crypto.ed25519 as ed

    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", 100)
    monkeypatch.setattr(ed, "MSM_BATCH_CUTOVER", 100)


def test_the_reference_imports_nothing_of_the_program():
    with open(refbad.__file__) as f:
        source = f.read()
    assert "tendermint_tpu" not in source.split('"""', 2)[2]
    assert not [ln for ln in source.splitlines() if ln.startswith(("import ", "from "))
                and ln.split()[1].split(".")[0] not in ("__future__", "random", "dataclasses")]


def test_the_schedule_is_one_height_in_two_with_rows_on_both_sides_of_the_prefix():
    config = dict(tiny_config(), validators=1000, blocks=130)
    lies = refbad.schedule(config, 2147950101)
    assert lies == refbad.schedule(config, 2147950101) != refbad.schedule(config, 2147950102)
    heights = [lie.height for lie in lies]
    assert len(heights) == 63 == len(set(heights)) and heights == sorted(heights)
    assert 3 <= heights[0] and heights[-1] <= 128
    assert refbad.light_prefix([10] * 1000) == 667 and refbad.light_prefix([10] * 24) == 17
    for lie in lies:
        assert 0 <= lie.row < 1000
        assert (lie.kind, lie.pair) == ((refbad.SIGNATURE, lie.height - 1) if lie.row < 667
                                        else (refbad.BLOCK_ID, lie.height))
    tail = sum(lie.kind == refbad.BLOCK_ID for lie in lies)
    assert 8 <= tail <= 34  # a third of 63, drawn
    assert set(refbad.refusable(lies)) == {lie.pair for lie in lies}


def test_the_liars_store_differs_from_the_source_where_the_schedule_says_and_nowhere_else():
    """Each lie by the reference's own verdicts: inside the prefix the
    light rule refuses the served commit; beyond it the light rule
    accepts, the full rule refuses, and the header is the source's."""
    config = tiny_config()
    chain = chainlib.build(config, 77)
    lies = refbad.schedule(config, 77)
    store = chain_badpeer.liar_store(chain, lies)
    by_height = {lie.height: lie for lie in lies}
    assert {lie.kind for lie in lies} == {refbad.SIGNATURE, refbad.BLOCK_ID}
    assert store.height() == chain.height == config["blocks"]
    for h in range(1, chain.height + 1):
        served, honest = store.load_block(h), chain.block_store.load_block(h)
        assert served.hash() == honest.hash() == chain.block_hashes[h - 1]
        differing = [i for i, (a, b) in enumerate(zip(served.last_commit.signatures,
                                                      honest.last_commit.signatures))
                     if a.signature != b.signature]
        lie = by_height.get(h)
        assert differing == ([lie.row] if lie else [])
        same_parts = (store.load_block_meta(h).block_id.part_set_header
                      == chain.block_store.load_block_meta(h).block_id.part_set_header)
        assert same_parts == (lie is None)
        if lie:
            sigs, msgs = chainlib.commit_values(chain, served.last_commit)
            light, _ = ref.commit_verdict(chain.pubkeys, chain.powers, sigs, msgs, 2, 3, True)
            full, _ = ref.commit_verdict(chain.pubkeys, chain.powers, sigs, msgs, 2, 3, False)
            assert (light, full) == (lie.kind == refbad.BLOCK_ID, False)


def test_the_cell_is_correct_and_its_joiners_refuse_by_the_schedule(badpeer_root, host_route,
                                                                    capsys):
    code, result = run_cell(badpeer_root, CELL, seconds=6.0, capsys=capsys)
    assert code == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"sync_rate", "setup_s"}
    assert set(result["compared"]) >= {
        "blocks_differing_from_source", "applied_commits_the_reference_refuses",
        "passes_halted", "peers_blamed_outside_the_rule", "lies_caught_at_the_wrong_stage",
        "windows_without_a_refusal", "refusal_faults"}
    assert all(c["value"] == 0 for c in result["compared"].values())


def test_a_traced_run_reports_the_five_new_readers(badpeer_root, host_route, capsys):
    code, result = run_cell(badpeer_root, CELL, seconds=6.0, trace=1, capsys=capsys)
    assert code == 0 and result["correct"] is True
    values = {name: m["value"] for name, m in result["metrics"].items()}
    assert set(NEW) <= set(values)
    assert values["refusals_per_100_blocks.sync"] > 0 and values["refusal_ms_per_block.sync"] > 0
    assert 0 < values["dropped_blocks_share.sync"] < 100
    assert values["peer_out_ms.sync"] > 0
    assert 0 <= values["verify_ahead_stale_share.sync"] <= 100
    assert {"apply_ms_per_block.sync", "pool_starved_share.sync",
            "engine_device_rows_share.sync"} <= set(values)
    assert "rlc_scalars_ms_per_launch.sync" not in values


@pytest.mark.parametrize("fault,may_halt", [
    # the block ID taken for the header's hash alone: the lie beyond the prefix gets past
    # the commit and is stopped by the validation before save_block, one stage late
    # (`lies_caught_at_the_wrong_stage`, where a window meets one); nothing halts
    ("parts_unchecked", False),
    # ... and with that validation passing whatever it is given too, the program is the
    # parent's: the block is saved, apply_block refuses it, the node halts
    ("parts_and_tail_unchecked", True),
    # a lie in the second half of the light batch, or any at all, taken for sound: the
    # joiner stores what the reference refuses and goes on
    ("half_batch", False),
    ("lowered_verify", False),
])
def test_correct_comes_out_false_under_the_controls(badpeer_root, host_route, capsys, fault,
                                                    may_halt):
    """Every control is seen by the probes, whatever the window's
    joiners happened to be served."""
    faults_badpeer.register()
    undo = []
    try:
        code, result = run_cell(badpeer_root, CELL, seconds=5.0, capsys=capsys,
                                before_window=lambda: undo.append(faults.FAULTS[fault]()))
    finally:
        for u in undo:
            u()
    assert code == 0 and result["correct"] is False
    failing = {name for name, c in result["compared"].items() if c["value"] > c["limit"]}
    assert "refusal_faults" in failing, result["compared"]
    assert may_halt or "passes_halted" not in failing, result["compared"]


def test_tail_unchecked_alone_changes_nothing_the_block_id_stands_before_it(
        badpeer_root, host_route, capsys):
    """The finding: no lie a peer can serve reaches the validation
    before save_block, so leaving it out alone is not seen by any
    number of this cell."""
    faults_badpeer.register()
    undo = []
    try:
        code, result = run_cell(badpeer_root, CELL, seconds=5.0, capsys=capsys,
                                before_window=lambda: undo.append(faults.FAULTS["tail_unchecked"]()))
    finally:
        for u in undo:
            u()
    assert code == 0 and result["correct"] is True


@pytest.mark.parametrize("name,window,value", [
    ("refusals_per_100_blocks", {"ops": 200, "refusals_commit": 30.0, "refusals_block": 1.0}, 15.5),
    ("refusal_ms_per_block", {"ops": 200, "refusal_s": 0.5}, 2.5),
    ("dropped_blocks_share", {"blocks_received": 400.0, "blocks_dropped": 100.0}, 25.0),
    ("peer_out_ms", {"peer_returns": 4.0, "peer_out_s": 1.0}, 250.0),
    ("peer_out_ms", {"peer_returns": 0.0, "peer_out_s": 0.0}, 0.0),
    ("verify_ahead_stale_share", {"verify_ahead_used": 90.0, "verify_ahead_stale": 10.0}, 10.0),
    # a program, or a driver, without the counters: nothing to read, and no error
    ("refusals_per_100_blocks", {"ops": 200}, None),
    ("refusal_ms_per_block", {"ops": 200}, None),
    ("dropped_blocks_share", {"ops": 200}, None),
    ("peer_out_ms", {"ops": 200}, None),
    ("verify_ahead_stale_share", {"ops": 200}, None),
])
def test_the_new_readers_on_a_hand_made_window(name, window, value):
    reader = importlib.import_module("benchmark.metrics." + name)
    assert reader.read({"window": window, "spans": [], "device": None}) == value
