"""A node block-syncing a chain whose validator set rotates (one key a
block), held to the benchmark's plain reference
(`benchmark/reference_churn.py`): the joiner's application handshaken
first, the commits verified on the device route with a pubkey-cache fill
for each key never seen, every stored set against the rotation rule,
and a forged commit at a rotated height refused. With them the spans on
the state's update and save and the counters on the cache's fills. What
`blocksync-1k-churn` runs on the chip, at a size that compiles here.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp

import tendermint_tpu.crypto.ed25519 as ed
from benchmark import chain as chainlib
from benchmark import chain_churn
from benchmark import reference_churn as refc
from benchmark.drivers import blocksync as base
from benchmark.drivers.blocksync_churn import Pass, Traffic, fill_counters
from benchmark.tools import faults_sync_churn
from tendermint_tpu import trace
from tendermint_tpu.ops import verify as V

# 8 equal validators: VerifyCommitLight reads 6 rows, the full check 8,
# both one 8-row launch; a change in block H acts from H + 2, so every
# height from 3 on brings one key the cache has not seen.
CONFIG = {"validators": 8, "voting_power": 10, "txs_per_block": 2, "chain_id": "bs-churn",
          "blocks": 16, "rotation": {"validators_per_block": 1}}
PARAMS = {"warm_up_blocks": 4, "check_sample": 4, "refusal_heights": [8, 12]}
SEED = 2147483671  # past 31 bits, as the driver's seeds are


@pytest.fixture(scope="module")
def traffic():
    t = Traffic(CONFIG, PARAMS, SEED)
    t.build()
    return t


@pytest.fixture
def device_route(monkeypatch):
    """Every commit on the cached per-signature kernel, behind the cache."""
    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", 4)
    monkeypatch.setattr(ed, "MSM_BATCH_CUTOVER", 100)
    monkeypatch.setenv("TM_TPU_PK_CACHE", "on")


def _sync(p, seconds: float = 300.0):
    p.start()
    done = p.done.wait(seconds)
    p.stop()
    return done


# ------------------------------------------------- a joiner against the reference


def test_a_joiner_syncs_the_rotating_chain_to_the_reference(traffic, device_route, monkeypatch):
    """One pass to the chain's end, then the driver's own `check()` over
    it: the source's block hashes, the reference's app hash, every
    sampled commit verified by the reference with its height's set, the
    joiner's three sets and its stored ones the schedule's; and the two
    refusal probes. The first sync of this chain's keys: a fill a block."""
    chain, sets = traffic.chain, traffic.sets
    monkeypatch.setattr(traffic, "passes", [])
    before = fill_counters()
    p = Pass(chain)
    traffic.passes.append(p)
    assert _sync(p) and p.caught_up
    grown = {key: value - before[key] for key, value in fill_counters().items()}
    height = p.block_store.height()
    assert height >= chain.height - 1 and p.fatal is None and not p.peer_errors
    for h in range(1, height + 3):
        stored = p.state_store.load_validators(h)
        assert refc.validator_set_hash(
            [(v.pub_key.bytes(), v.voting_power) for v in stored.validators]) == sets.hash_at(h), h
    # every key of every set a launch looked up was filled once, each fill at the 8-row bucket
    # (or 16, the light batch and the full one coalesced)
    assert grown["pk_filled_keys"] >= CONFIG["validators"] + height - 3
    assert grown["pk_fills"] >= 1 and grown["pk_fill_s"] > 0.0
    assert 8 * grown["pk_fills"] <= grown["pk_fill_rows"] <= 16 * grown["pk_fills"]
    assert p.verify_ahead().get("stale", 0.0) == 0.0 and p.verify_ahead()["used"] > 0
    checks, attempted, failed = traffic.check()
    assert {c.name: c.value for c in checks} == dict.fromkeys(
        ["blocks_differing_from_source", "headers_differing_from_reference_hash",
         "applied_commits_the_reference_refuses", "app_hash_or_height_wrong",
         "validator_sets_differing_from_schedule", "passes_halted_or_blaming_an_honest_peer",
         "refusal_faults"], 0)
    assert failed == 0 and attempted == height + 2


@pytest.mark.parametrize("half", [0, 1], ids=["first_half", "second_half"])
def test_a_corrupted_commit_at_a_rotated_height_is_refused_and_blames_the_server(
        traffic, device_route, half):
    sets, commit_height = traffic.sets, 10
    assert sets.set_at(commit_height) != sets.set_at(2)  # the rotation has moved this set
    _, rows = refc.light_rows(sets.set_at(commit_height), [True] * CONFIG["validators"])
    bad_index = rows[len(rows) // 2:][0] if half else rows[: len(rows) // 2][-1]
    record = traffic.probe(commit_height, bad_index)
    assert record["faults"] == 0 and record["reference_accepts"] is False
    assert record["joiner_height"] == commit_height - 1 and record["fatal"] is None
    assert record["peer_errors"] and record["peer_errors"][0].startswith("ValueError")
    assert f"wrong signature (#{bad_index})" in record["peer_errors"][0]


def test_a_joiner_whose_app_skipped_init_chain_refuses_the_chain(traffic, device_route):
    """Why the joiner's application is handshaken: a bare kvstore does
    not know the genesis set, refuses block 1's `val:<leaver>!0`, and its
    results hash parts from the chain's at height 2."""
    p = base.Pass(traffic.chain, stop_on_peer_error=True)
    assert _sync(p)
    assert p.block_store.height() == 1 and p.fatal is None
    assert p.peer_errors and "LastResultsHash" in str(p.peer_errors[0].err)


def test_a_joiner_that_breaks_the_h_plus_2_rule_holds_sets_the_schedule_does_not(
        traffic, device_route, monkeypatch):
    """The cell's upper control (`benchmark/tools/faults_sync_churn.py`):
    a change acting from H + 1 leaves the joiner a set one rotation
    ahead, which refuses the honest commit of the next height."""
    monkeypatch.setattr(traffic, "passes", [])
    undo = faults_sync_churn.changes_at_once()
    try:
        p = Pass(traffic.chain, stop_on_peer_error=True)
        traffic.passes.append(p)
        assert _sync(p)
        values = {c.name: c.value for c in traffic.check()[0]}
    finally:
        undo()
    assert values["validator_sets_differing_from_schedule"] >= 1
    assert values["passes_halted_or_blaming_an_honest_peer"] == 1


# ------------------------------------------------- the spans on the state's update and save


@pytest.mark.parametrize("build,changes,full", [
    (chain_churn.build, 2, 1),  # a leaver and a joiner a block; the set it makes stored whole
    (chainlib.build, 0, 0),  # no change: a pointer to the height the set last changed at
], ids=["rotating", "static"])
def test_each_block_opens_state_update_and_state_save_under_apply_block(build, changes, full):
    config = dict(CONFIG, validators=4, blocks=3)
    was = trace.enabled()
    trace.set_enabled(True)
    trace.clear()
    try:
        build(config, SEED)
        events = [ev for ev in trace.export()["traceEvents"] if ev.get("ph") == "X"]
    finally:
        trace.set_enabled(was)
        trace.clear()
    applies = {ev["args"]["span"]: ev["args"]["height"] for ev in events
               if ev["name"] == "state.apply_block"}
    for name, key, want in (("state.update", "changes", changes),
                            ("state.save", "full_sets_written", full)):
        spans = [ev["args"] for ev in events if ev["name"] == name]
        blocks = [a for a in spans if a["height"] > 0]
        assert [(applies.get(a["parent"]), a[key]) for a in blocks] == [(h, want) for h in (1, 2, 3)]
        # the genesis state, saved before the first block, outside any block
        assert [a[key] for a in spans if a["height"] == 0] == ([1] if name == "state.save" else [])


# ------------------------------------------------- the counters on the cache's fills


def _stub_tables(enc):
    """A table build that launches nothing: the counters count."""
    return (jnp.zeros((enc.shape[0], V.PK_SPLITS, 16, 4, 32), jnp.int16),
            jnp.ones((enc.shape[0],), bool))


def _keys(lo: int, hi: int) -> list[bytes]:
    return [i.to_bytes(4, "big") * 8 for i in range(lo, hi)]


def _fill_samples() -> dict:
    m = V._engine_metrics()
    return {attr: sum(value for _, labels, value in getattr(m, attr).samples()
                      if labels["plane"] == "fill_pk")
            for attr in ("pk_cache_fills", "pk_cache_filled_keys", "pk_cache_fill_rows",
                         "pk_cache_fill_seconds")}


@pytest.mark.parametrize("batch,keys", [
    (_keys(0, 9) + _keys(100, 103), 3),  # 12 rows, 3 of them new: one fill at 16 rows
    (_keys(0, 8) + _keys(200, 202) * 6, 2),  # 20 rows, 2 new keys six times each: 32 rows
    (_keys(0, 8) * 3, 0),  # every key cached: no fill
], ids=["three_new_in_twelve", "two_new_keys_repeated", "hit_only"])
def test_a_fill_counts_one_fill_its_keys_its_padded_rows_and_its_time(batch, keys):
    cache = V.PubkeyCache(capacity=64, build_fn=_stub_tables, plane="fill_pk")
    cache.ensure_snapshot(_keys(0, 9))
    before = _fill_samples()
    slots, _, _ = cache.ensure_snapshot(batch)
    grown = {attr: value - before[attr] for attr, value in _fill_samples().items()}
    assert slots is not None and len(slots) == len(batch)
    if not keys:
        assert grown == dict.fromkeys(grown, 0.0)
        return
    assert grown["pk_cache_fills"] == 1 and grown["pk_cache_filled_keys"] == keys
    assert grown["pk_cache_fill_rows"] == V._pad_pow2(len(batch))
    assert grown["pk_cache_fill_seconds"] > 0.0
