"""State layer tests: genesis state, block/state stores, BlockExecutor
end-to-end against the kvstore app (ref: internal/state/execution_test.go,
store_test.go; internal/store/store_test.go)."""

import pytest

from helpers import make_genesis_doc, make_keys, sign_commit
from tendermint_tpu.abci import LocalClient
from tendermint_tpu.abci.kvstore import KVStoreApplication
from tendermint_tpu.state import BlockExecutor, StateStore, make_genesis_state
from tendermint_tpu.state.validation import InvalidBlockError
from tendermint_tpu.store.blockstore import BlockStore
from tendermint_tpu.store.kv import MemDB
from tendermint_tpu.types.block import BlockID, Commit
from tendermint_tpu.types.part_set import PartSet
from tendermint_tpu.utils.tmtime import Time

CHAIN_ID = "exec-test-chain"


def make_chain_fixtures(n_vals=4):
    keys = make_keys(n_vals)
    gen_doc = make_genesis_doc(keys, CHAIN_ID)
    state = make_genesis_state(gen_doc)
    app = KVStoreApplication()
    client = LocalClient(app)
    state_store = StateStore(MemDB())
    block_store = BlockStore(MemDB())
    state_store.save(state)
    executor = BlockExecutor(state_store, client, block_store=block_store)
    return keys, state, executor, state_store, block_store, app


def propose_and_apply(keys, state, executor, block_store, txs, last_commit, height, t_ns):
    proposer = state.validators.get_proposer()
    block = state.make_block(
        height, txs, last_commit, [], proposer.address, Time.from_unix_ns(t_ns)
    )
    part_set = PartSet.from_data(block.to_proto().encode(), 65536)
    block_id = BlockID(hash=block.hash(), part_set_header=part_set.header)
    new_state = executor.apply_block(state, block_id, block)
    seen_commit = sign_commit(CHAIN_ID, new_state.validators, keys, height, 0, block_id)
    block_store.save_block(block, part_set, seen_commit)
    return new_state, block_id


def test_genesis_state():
    keys = make_keys(4)
    state = make_genesis_state(make_genesis_doc(keys, CHAIN_ID))
    assert state.chain_id == CHAIN_ID
    assert state.last_block_height == 0
    assert state.validators.size() == 4
    assert state.next_validators.size() == 4
    assert state.last_validators.size() == 0


def test_apply_blocks_advances_state():
    keys, state, executor, state_store, block_store, app = make_chain_fixtures()
    base_t = 1_700_000_001 * 10**9

    s1, bid1 = propose_and_apply(keys, state, executor, block_store, [b"a=1"], Commit(height=0), 1, base_t)
    assert s1.last_block_height == 1
    assert s1.app_hash != b""
    assert app.height == 1

    commit1 = sign_commit(CHAIN_ID, s1.last_validators, keys, 1, 0, bid1)
    s2, bid2 = propose_and_apply(keys, s1, executor, block_store, [b"b=2", b"c=3"], commit1, 2, base_t + 10**9)
    assert s2.last_block_height == 2
    assert s2.last_results_hash != s1.last_results_hash or True
    assert app.height == 2

    # stores are consistent
    assert block_store.height() == 2
    loaded = block_store.load_block(1)
    assert loaded is not None and loaded.header.height == 1
    assert block_store.load_block_commit(1) is not None
    reloaded_state = state_store.load()
    assert reloaded_state.last_block_height == 2
    assert reloaded_state.app_hash == s2.app_hash
    assert reloaded_state.validators.hash() == s2.validators.hash()


def test_apply_block_rejects_bad_last_commit():
    keys, state, executor, state_store, block_store, app = make_chain_fixtures()
    base_t = 1_700_000_001 * 10**9
    s1, bid1 = propose_and_apply(keys, state, executor, block_store, [b"a=1"], Commit(height=0), 1, base_t)

    # commit signed over the WRONG block id
    from helpers import make_block_id

    bad_commit = sign_commit(CHAIN_ID, s1.last_validators, keys, 1, 0, make_block_id(b"\xbb" * 32))
    proposer = s1.validators.get_proposer()
    block = s1.make_block(2, [], bad_commit, [], proposer.address, Time.from_unix_ns(base_t + 10**9))
    from tendermint_tpu.types.part_set import PartSet as PS

    ps = PS.from_data(block.to_proto().encode(), 65536)
    with pytest.raises((InvalidBlockError, ValueError)):
        executor.apply_block(s1, BlockID(hash=block.hash(), part_set_header=ps.header), block)


def test_validator_update_takes_effect_at_h_plus_2():
    keys, state, executor, state_store, block_store, app = make_chain_fixtures()
    base_t = 1_700_000_001 * 10**9
    from tendermint_tpu.abci.kvstore import make_validator_tx
    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey

    new_key = Ed25519PrivKey.generate(b"\x77" * 32)
    tx = make_validator_tx(new_key.pub_key().bytes(), 5)

    s1, bid1 = propose_and_apply(keys, state, executor, block_store, [tx], Commit(height=0), 1, base_t)
    # H=1 included the update: validators (H+1 set) unchanged, next_validators has 5
    assert s1.validators.size() == 4
    assert s1.next_validators.size() == 5
    assert s1.last_height_validators_changed == 3

    commit1 = sign_commit(CHAIN_ID, s1.last_validators, keys, 1, 0, bid1)
    s2, _ = propose_and_apply(keys, s1, executor, block_store, [], commit1, 2, base_t + 10**9)
    assert s2.validators.size() == 5


def test_process_proposal_roundtrip():
    keys, state, executor, state_store, block_store, app = make_chain_fixtures()
    proposer = state.validators.get_proposer()
    block = executor.create_proposal_block(1, state, Commit(height=0), proposer.address, Time.from_unix_ns(1_700_000_001 * 10**9))
    assert block.header.height == 1
    assert executor.process_proposal(block, state)


# ---- last-commit info from the held state (ISSUE 30) ------------------
#
# A chain on which every answer of build_last_commit_info differs from
# its neighbours': a validator joins (tx at 2, in force from 4), one
# changes power (tx at 4, in force from 6), and the commits for heights
# 2 and 6 each lack one signature.

CI_HEIGHTS = 8
CI_JOIN_AT, CI_POWER_AT = 2, 4
CI_ABSENT_IN_COMMIT_FOR = (2, 6)


@pytest.fixture(scope="module")
def commit_info_chain():
    from types import SimpleNamespace

    from tendermint_tpu.abci.kvstore import make_validator_tx
    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey

    keys, state, executor, state_store, block_store, _ = make_chain_fixtures()
    joiner = Ed25519PrivKey.generate(b"\x77" * 32)
    keys = keys + [joiner]
    txs_at = {
        CI_JOIN_AT: [make_validator_tx(joiner.pub_key().bytes(), 5)],
        CI_POWER_AT: [make_validator_tx(keys[0].pub_key().bytes(), 25)],
    }
    base_t = 1_700_000_001 * 10**9
    before, blocks = {}, {}
    commit, bid = Commit(height=0), None
    for h in range(1, CI_HEIGHTS + 1):
        if h > 1:
            signers = keys[:3] + keys[4:] if h - 1 in CI_ABSENT_IN_COMMIT_FOR else keys
            commit = sign_commit(CHAIN_ID, state.last_validators, signers, h - 1, 0, bid)
        before[h] = state
        proposer = state.validators.get_proposer()
        blocks[h] = state.make_block(h, txs_at.get(h, []), commit, [], proposer.address,
                                     Time.from_unix_ns(base_t + h * 10**9))
        state, bid = propose_and_apply(keys, state, executor, block_store, txs_at.get(h, []), commit, h,
                                       base_t + h * 10**9)
    # the set that signed the commit in block h: the join in force from 4, the power change from 6
    assert [before[h].last_validators.size() for h in (4, 5)] == [4, 5]
    assert [before[h].last_validators.total_voting_power() for h in (6, 7)] == [45, 60]
    return SimpleNamespace(executor=executor, store=state_store, before=before, blocks=blocks, tip=state)


def commit_info_from_store(store, block):
    """What build_last_commit_info answered before ISSUE 30: the set
    re-derived from the store, whatever the caller held."""
    from tendermint_tpu.abci import types as abci

    vals = store.load_validators(block.header.height - 1)
    commit = block.last_commit
    assert commit.size() == vals.size()
    return abci.CommitInfo(
        round=commit.round,
        votes=[
            abci.VoteInfo(validator=abci.Validator(address=v.address, power=v.voting_power),
                          signed_last_block=not commit.signatures[i].absent())
            for i, v in enumerate(vals.validators)
        ],
    )


@pytest.fixture
def store_reads(commit_info_chain, monkeypatch):
    """The heights StateStore.load_validators was asked for."""
    reads = []
    load = commit_info_chain.store.load_validators
    monkeypatch.setattr(commit_info_chain.store, "load_validators", lambda h: reads.append(h) or load(h))
    return reads


@pytest.mark.parametrize("source", ["state", "store"])
@pytest.mark.parametrize("height", range(1, CI_HEIGHTS + 1))
def test_commit_info_equals_the_stores_answer(commit_info_chain, store_reads, height, source):
    """Held state (the block is the state's next) and the fallback (an
    older block while the state is ahead, the handshake's
    _exec_block_on_app) both give, field for field, the CommitInfo built
    from store.load_validators(height - 1); only the fallback reads it."""
    c = commit_info_chain
    block = c.blocks[height]
    state = c.before[height] if source == "state" else c.tip
    info = c.executor.build_last_commit_info(block, state)
    if height == 1:
        assert info == type(info)() and store_reads == []
        return
    assert store_reads == ([] if source == "state" else [height - 1])
    assert info == commit_info_from_store(c.store, block)
    assert [v.signed_last_block for v in info.votes].count(False) == (height - 1 in CI_ABSENT_IN_COMMIT_FOR)


@pytest.mark.parametrize("entry", ["apply_block", "process_proposal"])
def test_next_block_never_reads_validators_from_the_store(entry):
    """The O(height) re-derivation cannot come back unnoticed: with the
    block at last_block_height + 1, neither entry point may reach
    StateStore.load_validators."""
    keys, state, executor, state_store, block_store, _ = make_chain_fixtures()
    base_t = 1_700_000_001 * 10**9
    s, bid = propose_and_apply(keys, state, executor, block_store, [], Commit(height=0), 1, base_t)
    reads = []
    state_store.load_validators = lambda h: reads.append(h)  # returns None: a read would also raise
    for h in (2, 3):
        commit = sign_commit(CHAIN_ID, s.last_validators, keys, h - 1, 0, bid)
        if entry == "process_proposal":
            proposer = s.validators.get_proposer()
            block = s.make_block(h, [], commit, [], proposer.address, Time.from_unix_ns(base_t + h * 10**9))
            assert executor.process_proposal(block, s)
        s, bid = propose_and_apply(keys, s, executor, block_store, [], commit, h, base_t + h * 10**9)
    assert reads == []


@pytest.mark.parametrize("source", ["state", "store", "store-missing"])
def test_commit_info_refusals_are_unchanged(commit_info_chain, source):
    """A commit of another size than the set, from either source, and a
    height the store does not hold raise the RuntimeErrors they raised."""
    import copy

    c = commit_info_chain
    block = copy.deepcopy(c.blocks[5])
    state = c.before[5] if source == "state" else c.tip
    executor = c.executor
    if source == "store-missing":
        executor = BlockExecutor(StateStore(MemDB()), None)
        match = "failed to load validator set at height 4"
    else:
        block.last_commit.signatures.pop()
        match = r"commit size \(4\) doesn't match validator set length \(5\) at height 5"
    with pytest.raises(RuntimeError, match=match):
        executor.build_last_commit_info(block, state)


def test_commit_info_span_and_counter_name_the_source():
    """apply_block opens state.commit_info under its state.finalize_block
    span, an older block replayed against a state that is ahead reads
    source="store", the initial height builds nothing; and
    commit_info_total{source} counts one per span."""
    from tendermint_tpu import trace as T
    from tendermint_tpu.metrics import Registry, StateMetrics

    keys, state, executor, _, block_store, _ = make_chain_fixtures()
    executor.metrics = StateMetrics(Registry())
    base_t = 1_700_000_001 * 10**9
    was = T.enabled()
    T.set_enabled(True)
    T.clear()
    try:
        s1, bid1 = propose_and_apply(keys, state, executor, block_store, [], Commit(height=0), 1, base_t)
        commit1 = sign_commit(CHAIN_ID, s1.last_validators, keys, 1, 0, bid1)
        s2, _ = propose_and_apply(keys, s1, executor, block_store, [], commit1, 2, base_t + 10**9)
        executor.build_last_commit_info(block_store.load_block(2), s2)
        events = {}
        for e in T.export()["traceEvents"]:
            if e["ph"] == "X":
                events.setdefault(e["name"], []).append(e["args"])
    finally:
        T.clear()
        T.set_enabled(was)
    held, replayed = events["state.commit_info"]
    assert (held["height"], held["source"]) == (2, "state")
    assert (replayed["height"], replayed["source"]) == (2, "store")
    assert held["parent"] == events["state.finalize_block"][1]["span"] != replayed["parent"]
    counted = {lbl["source"]: n for _, lbl, n in executor.metrics.commit_info.samples()}
    assert counted == {"state": 1, "store": 1}


def test_state_store_validator_lookup():
    keys, state, executor, state_store, block_store, app = make_chain_fixtures()
    base_t = 1_700_000_001 * 10**9
    s = state
    commit = Commit(height=0)
    bid = None
    for h in range(1, 5):
        if h > 1:
            commit = sign_commit(CHAIN_ID, s.last_validators, keys, h - 1, 0, bid)
        s, bid = propose_and_apply(keys, s, executor, block_store, [], commit, h, base_t + h * 10**9)
    for h in range(1, 5):
        vals = state_store.load_validators(h)
        assert vals is not None, f"no validators at height {h}"
        assert vals.size() == 4


def test_finalize_block_responses_roundtrip():
    keys, state, executor, state_store, block_store, app = make_chain_fixtures()
    s1, _ = propose_and_apply(keys, state, executor, block_store, [b"x=y"], Commit(height=0), 1, 1_700_000_001 * 10**9)
    resp = state_store.load_finalize_block_responses(1)
    assert resp is not None
    assert len(resp.tx_results) == 1
    assert resp.tx_results[0].code == 0
    assert resp.app_hash == s1.app_hash


def test_block_store_pruning():
    keys, state, executor, state_store, block_store, app = make_chain_fixtures()
    base_t = 1_700_000_001 * 10**9
    s = state
    commit = Commit(height=0)
    bid = None
    for h in range(1, 6):
        if h > 1:
            commit = sign_commit(CHAIN_ID, s.last_validators, keys, h - 1, 0, bid)
        s, bid = propose_and_apply(keys, s, executor, block_store, [], commit, h, base_t + h * 10**9)
    pruned = block_store.prune_blocks(3)
    assert pruned == 2
    assert block_store.base() == 3
    assert block_store.load_block(2) is None
    assert block_store.load_block(3) is not None


def test_validate_block_rejects_every_mutated_header_field():
    """Table-driven rejection sweep for validateBlock
    (internal/state/validation.go:14): every consensus-critical header
    field a byzantine proposer could skew must individually fail
    validation — the happy path alone proves nothing about byzantine
    inputs."""
    import copy

    import pytest

    from test_consensus import CHAIN, fast_params, make_node, wait_for_height
    from helpers import make_genesis_doc, make_keys
    from tendermint_tpu.state.validation import InvalidBlockError, validate_block
    from tendermint_tpu.types.block import BlockID
    from tendermint_tpu.utils.tmtime import Time

    keys = make_keys(1)
    gen_doc = make_genesis_doc(keys, CHAIN)
    gen_doc.consensus_params = fast_params()
    node = make_node(keys, 0, gen_doc)
    node.start()
    try:
        assert wait_for_height([node], 3, timeout=60)
    finally:
        node.stop()
    h = node.block_store.height()
    # Block h must be validated against the state as of h-1; the state
    # store only holds the latest state, so reconstruct state(h-1) by
    # replaying a fresh node over a partial copy of the block store.
    from tendermint_tpu.abci import LocalClient
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.consensus import Handshaker
    from tendermint_tpu.state import StateStore, make_genesis_state
    from tendermint_tpu.store.blockstore import BlockStore
    from tendermint_tpu.store.kv import MemDB

    # replay a fresh node to h-1 only (partial store view)
    partial_store = BlockStore(MemDB())
    for height in range(1, h):
        meta = node.block_store.load_block_meta(height)
        blk = node.block_store.load_block(height)
        sc = node.block_store.load_seen_commit(height) or node.block_store.load_block_commit(height)
        parts = blk.make_part_set(65536)
        partial_store.save_block(blk, parts, sc)
    st0 = make_genesis_state(gen_doc)
    fresh_ss = StateStore(MemDB())
    fresh_ss.save(st0)
    hs = Handshaker(fresh_ss, st0, partial_store, gen_doc)
    state = hs.handshake(LocalClient(KVStoreApplication()))
    assert state.last_block_height == h - 1

    good = node.block_store.load_block(h)
    validate_block(state, copy.deepcopy(good))  # sanity: the real block passes

    def mutated(**changes):
        b = copy.deepcopy(good)
        for field, value in changes.items():
            setattr(b.header, field, value)
        # re-fill hashes the mutation invalidates? NO — the point is the
        # header as gossiped; validate_basic recomputes nothing
        return b

    cases = {
        "chain_id": dict(chain_id="other-chain"),
        "height": dict(height=h + 1),
        "app_hash": dict(app_hash=b"\x55" * 8),
        "consensus_hash": dict(consensus_hash=b"\x55" * 32),
        "last_results_hash": dict(last_results_hash=b"\x55" * 32),
        "validators_hash": dict(validators_hash=b"\x55" * 32),
        "next_validators_hash": dict(next_validators_hash=b"\x55" * 32),
        "proposer_address": dict(proposer_address=b"\x55" * 20),
        "version_app": dict(version_app=99),
        "time": dict(time=Time.from_unix_ns(state.last_block_time.unix_ns() - 1)),
        "last_block_id": dict(last_block_id=BlockID(hash=b"\x55" * 32)),
    }
    for name, changes in cases.items():
        with pytest.raises((InvalidBlockError, ValueError)):
            validate_block(state, mutated(**changes))
