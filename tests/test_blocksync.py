"""Blocksync tests (ref: internal/blocksync/pool_test.go, reactor_test.go)."""

from __future__ import annotations

import threading
import time

import pytest

from helpers import make_genesis_doc, make_keys
from test_consensus import fast_params, make_node, wait_for_height
from tendermint_tpu.blocksync import BlockSyncReactor, blocksync_channel_descriptor
from tendermint_tpu.blocksync.pool import BlockPool
from tendermint_tpu.blocksync.reactor import (
    BlockResponse,
    StatusResponse,
    decode_blocksync_msg,
    encode_blocksync_msg,
)
from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
from tendermint_tpu.p2p import (
    MemoryNetwork,
    NodeInfo,
    PeerManager,
    PeerManagerOptions,
    Router,
    node_id_from_pubkey,
)
from tendermint_tpu.p2p.transport import Endpoint

CHAIN = "bs-test-chain"


def test_pool_requests_and_ordering():
    sent = []
    pool = BlockPool(1, lambda h, p: sent.append((h, p)))
    pool.set_peer_range("aa" * 20, 1, 5)
    pool._fill_requests()
    assert sorted(h for h, _ in sent) == [1, 2, 3, 4, 5]
    assert pool.is_caught_up() is False  # nothing received yet → height 1 < 5


def test_pool_add_peek_pop():
    class FakeBlock:
        def __init__(self, h):
            class H:  # noqa
                height = h

            self.header = H()

    pool = BlockPool(1, lambda h, p: None)
    pool.set_peer_range("aa" * 20, 1, 3)
    pool._fill_requests()
    for h in (1, 2):
        assert pool.add_block("aa" * 20, FakeBlock(h))
    f, s = pool.peek_two_blocks()
    assert f.header.height == 1 and s.header.height == 2
    pool.pop_request()
    f, s = pool.peek_two_blocks()
    assert f.header.height == 2 and s is None


def test_pool_redo_request_bans_peer():
    class FakeBlock:
        def __init__(self, h):
            class H:  # noqa
                height = h

            self.header = H()

    pool = BlockPool(1, lambda h, p: None)
    pool.set_peer_range("aa" * 20, 1, 3)
    pool._fill_requests()
    pool.add_block("aa" * 20, FakeBlock(1))
    bad = pool.redo_request(1)
    assert bad == "aa" * 20
    assert "aa" * 20 not in pool.peers


def test_codec_roundtrip():
    from tendermint_tpu.blocksync.reactor import BlockRequest, NoBlockResponse, StatusRequest

    for msg in (BlockRequest(7), NoBlockResponse(9), StatusRequest(), StatusResponse(1, 42)):
        rt = decode_blocksync_msg(encode_blocksync_msg(msg))
        assert type(rt) is type(msg)
        for attr in ("height", "base"):
            if hasattr(msg, attr):
                assert getattr(rt, attr) == getattr(msg, attr)


class BSNode:
    """Node exposing only the blocksync reactor over the memory network."""

    def __init__(self, network, key_seed, cs_node, on_caught_up=None, block_sync=True):
        self.key = Ed25519PrivKey.generate(bytes([key_seed]) * 32)
        self.node_id = node_id_from_pubkey(self.key.pub_key())
        self.transport = network.create_transport(self.node_id)
        self.pm = PeerManager(self.node_id, PeerManagerOptions(max_connected=8))
        self.router = Router(
            NodeInfo(node_id=self.node_id, network=CHAIN), self.key, self.pm, [self.transport]
        )
        ch = self.router.open_channel(blocksync_channel_descriptor())
        self.reactor = BlockSyncReactor(
            cs_node.block_exec.store.load(),
            cs_node.block_exec,
            cs_node.block_store,
            ch,
            self.pm,
            on_caught_up=on_caught_up,
            block_sync=block_sync,
        )

    def start(self):
        self.router.start()
        self.reactor.start()

    def stop(self):
        self.reactor.stop()
        self.router.stop()


def test_blocksync_catches_up_from_peer():
    """A fresh node fast-syncs an existing chain from a serving peer —
    every height verified via VerifyCommitLight on the batch plane
    (ref: reactor_test.go TestReactor_SyncTime)."""
    keys = make_keys(1)
    gen_doc = make_genesis_doc(keys, CHAIN)
    gen_doc.consensus_params = fast_params()

    # build a chain of ≥5 blocks
    source = make_node(keys, 0, gen_doc)
    source.start()
    try:
        assert wait_for_height([source], 5, timeout=60)
    finally:
        source.stop()
    src_height = source.block_store.height()

    # fresh node (same genesis) with empty stores
    fresh = make_node(keys, 0, gen_doc)

    caught = {}
    done = threading.Event()

    def on_caught_up(state, n):
        caught["state"] = state
        caught["n"] = n
        done.set()

    net = MemoryNetwork()
    server = BSNode(net, 0x51, source, block_sync=False)
    client = BSNode(net, 0x52, fresh, on_caught_up=on_caught_up)
    server.start()
    client.start()
    try:
        client.pm.add(Endpoint(protocol="memory", host=server.node_id, node_id=server.node_id))
        assert done.wait(timeout=60), (
            f"client at {client.reactor.pool.height}, server at {src_height}"
        )
    finally:
        client.stop()
        server.stop()
    assert caught["n"] >= src_height - 1
    assert caught["state"].last_block_height >= src_height - 1
    # synced blocks byte-identical with the source chain
    for h in range(1, src_height):
        assert fresh.block_store.load_block(h).hash() == source.block_store.load_block(h).hash()


def test_blocksync_rejects_tampered_block():
    """A block whose commit doesn't verify is re-requested and the peer
    reported (ref: reactor.go:592-604)."""
    keys = make_keys(1)
    gen_doc = make_genesis_doc(keys, CHAIN)
    gen_doc.consensus_params = fast_params()
    source = make_node(keys, 0, gen_doc)
    source.start()
    try:
        assert wait_for_height([source], 3, timeout=60)
    finally:
        source.stop()

    fresh = make_node(keys, 0, gen_doc)
    errors = []

    class _Chan:
        def send_to(self, *a, **k):
            return True

        def send_error(self, e):
            errors.append(e)

        def broadcast(self, *a, **k):
            return True

        def receive_one(self, timeout=None):
            time.sleep(timeout or 0)
            return None

    class _PM:
        def subscribe(self, cb):
            pass

        def unsubscribe(self, cb):
            pass

    reactor = BlockSyncReactor(
        fresh.block_exec.store.load(), fresh.block_exec, fresh.block_store, _Chan(), _PM()
    )
    b1 = source.block_store.load_block(1)
    b2 = source.block_store.load_block(2)
    # tamper: swap block 1's data so the commit in b2 doesn't match
    b1.txs = [b"evil"]
    b1.header.data_hash = b"\x99" * 32
    peer = "ff" * 20
    reactor.pool.set_peer_range(peer, 1, 3)
    reactor.pool._fill_requests()
    reactor.pool.add_block(peer, b1)
    reactor.pool.add_block(peer, b2)
    assert reactor._try_sync_one() is False
    assert errors and errors[0].node_id == peer
    assert peer not in reactor.pool.peers


def _stub_reactor(fresh, errors):
    class _Chan:
        def send_to(self, *a, **k):
            return True

        def send_error(self, e):
            errors.append(e)

        def broadcast(self, *a, **k):
            return True

        def receive_one(self, timeout=None):
            time.sleep(timeout or 0)
            return None

    class _PM:
        def subscribe(self, cb):
            pass

        def unsubscribe(self, cb):
            pass

    return BlockSyncReactor(
        fresh.block_exec.store.load(), fresh.block_exec, fresh.block_store, _Chan(), _PM()
    )


def test_blocksync_verify_ahead_pipeline():
    """With >=3 blocks pooled, iteration h dispatches h+1's verification
    ahead (device kernel overlapping the host-side apply) and iteration
    h+1 consumes it via the identity/valset guards — same sync result,
    one verification per height either way."""
    keys = make_keys(1)
    gen_doc = make_genesis_doc(keys, CHAIN)
    gen_doc.consensus_params = fast_params()
    source = make_node(keys, 0, gen_doc)
    source.start()
    try:
        assert wait_for_height([source], 5, timeout=60)
    finally:
        source.stop()

    fresh = make_node(keys, 0, gen_doc)
    errors = []
    reactor = _stub_reactor(fresh, errors)
    peer = "aa" * 20
    src_height = source.block_store.height()
    reactor.pool.set_peer_range(peer, 1, src_height)
    reactor.pool._fill_requests()
    for h in range(1, src_height + 1):
        reactor.pool.add_block(peer, source.block_store.load_block(h))

    consumed = []
    orig_try = reactor._try_sync_one

    # track cache consumption: _verify_ahead is set after each iteration
    # that saw a third block, and consumed (reset to None) by the next
    for _ in range(src_height - 1):
        had_ahead = reactor._verify_ahead is not None
        assert orig_try() is True
        consumed.append(had_ahead)
    assert not errors
    # every iteration after the first (while a third block existed) hit the cache
    assert consumed[0] is False and any(consumed[1:]), consumed
    assert reactor.state.last_block_height == src_height - 1
    for h in range(1, src_height):
        assert fresh.block_store.load_block(h).hash() == source.block_store.load_block(h).hash()


def test_blocksync_verify_ahead_detects_tampering():
    """A tampered block whose bad commit was dispatched through the
    verify-ahead path still fails verification and bans the senders."""
    keys = make_keys(1)
    gen_doc = make_genesis_doc(keys, CHAIN)
    gen_doc.consensus_params = fast_params()
    source = make_node(keys, 0, gen_doc)
    source.start()
    try:
        assert wait_for_height([source], 4, timeout=60)
    finally:
        source.stop()

    fresh = make_node(keys, 0, gen_doc)
    errors = []
    reactor = _stub_reactor(fresh, errors)
    peer = "bb" * 20
    reactor.pool.set_peer_range(peer, 1, 4)
    reactor.pool._fill_requests()
    b1 = source.block_store.load_block(1)
    b2 = source.block_store.load_block(2)
    b3 = source.block_store.load_block(3)
    # tamper block 2: the ahead-dispatch for height 2 (fired while height
    # 1 processes, proven by b3.last_commit) must reject it
    b2.txs = [b"evil"]
    b2.header.data_hash = b"\x88" * 32
    reactor.pool.add_block(peer, b1)
    reactor.pool.add_block(peer, b2)
    reactor.pool.add_block(peer, b3)
    assert reactor._try_sync_one() is True  # height 1 OK; dispatches ahead for 2
    assert reactor._verify_ahead is not None
    assert reactor._try_sync_one() is False  # ahead completion raises
    assert errors and errors[0].node_id == peer


def test_blocksync_device_failure_is_fatal_not_a_lying_peer(monkeypatch):
    """Only a verification verdict blames the peers. Anything else that
    escapes commit verification — a JAX runtime error above all — is
    this node's own fault: it goes to on_fatal, bans nobody and refetches
    nothing (it used to ban both senders and refetch, forever)."""
    from tendermint_tpu.blocksync import fixture
    from tendermint_tpu.blocksync import reactor as reactor_mod

    chain = fixture.build_chain(5, 4, 3)
    state, executor, _, block_store = fixture._executor(chain.gen_doc)
    errors, fatal = [], []

    class _Chan:
        def send_to(self, *a, **k):
            return True

        def send_error(self, e):
            errors.append(e)

    class _PM:
        def subscribe(self, cb):
            pass

        def unsubscribe(self, cb):
            pass

    reactor = BlockSyncReactor(state, executor, block_store, _Chan(), _PM(),
                               on_fatal=fatal.append)
    peer = "cc" * 20
    reactor.pool.set_peer_range(peer, 1, 3)
    reactor.pool._fill_requests()
    for h in (1, 2, 3):
        reactor.pool.add_block(peer, chain.block_store.load_block(h))

    class XlaRuntimeError(RuntimeError):
        pass

    def broken_device(*a, **k):
        raise XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory while compiling")

    monkeypatch.setattr(reactor_mod, "verify_commit_light", broken_device)
    reactor._pool_routine()  # returns by itself: the fatal path ends the loop
    assert len(fatal) == 1 and isinstance(fatal[0], XlaRuntimeError)
    assert reactor.sync_error is True
    assert errors == [] and peer in reactor.pool.peers
    assert reactor.pool.height == 1 and block_store.height() == 0

    # the same failure arriving through the verify-ahead completion
    monkeypatch.undo()
    fatal.clear()
    reactor = BlockSyncReactor(state, executor, block_store, _Chan(), _PM(),
                               on_fatal=fatal.append)
    reactor.pool.set_peer_range(peer, 1, 3)
    reactor.pool._fill_requests()
    for h in (1, 2, 3):
        reactor.pool.add_block(peer, chain.block_store.load_block(h))
    monkeypatch.setattr(reactor_mod, "verify_commit_light_async",
                        lambda *a, **k: broken_device)
    assert reactor._try_sync_one() is True  # height 1 verifies; 2 is dispatched ahead
    reactor._pool_routine()
    assert len(fatal) == 1 and isinstance(fatal[0], XlaRuntimeError)
    assert errors == [] and peer in reactor.pool.peers and block_store.height() == 1


def test_blocksync_carries_extended_commits():
    """Blocks synced through extension-enabled heights arrive with their
    ExtendedCommit and the syncing node persists it, so it can itself
    serve extension-aware catch-up gossip later (ref: blocksync
    BlockResponse.ext_commit, store SaveBlockWithExtendedCommit)."""
    import dataclasses

    from tendermint_tpu.types.params import ABCIParams

    keys = make_keys(1)
    gen_doc = make_genesis_doc(keys, CHAIN)
    gen_doc.consensus_params = dataclasses.replace(
        fast_params(), abci=ABCIParams(vote_extensions_enable_height=2)
    )
    source = make_node(keys, 0, gen_doc)
    source.start()
    try:
        assert wait_for_height([source], 5, timeout=60)
    finally:
        source.stop()
    src_height = source.block_store.height()
    assert source.block_store.load_extended_commit(3), "source has no ext commit"

    fresh = make_node(keys, 0, gen_doc)
    errors = []
    reactor = _stub_reactor(fresh, errors)
    peer = "cc" * 20
    reactor.pool.set_peer_range(peer, 1, src_height)
    reactor.pool._fill_requests()
    for h in range(1, src_height + 1):
        reactor.pool.add_block(
            peer,
            source.block_store.load_block(h),
            ext_commit=source.block_store.load_extended_commit_proto(h),
        )
    for _ in range(src_height - 1):
        assert reactor._try_sync_one() is True
    assert not errors
    # the synced node persisted the extended commits for served heights
    for h in range(2, src_height - 1):
        votes = fresh.block_store.load_extended_commit(h)
        assert votes, f"no extended commit persisted at {h}"
        assert any(v is not None and v.extension_signature for v in votes)


def test_validate_ext_commit_rules():
    """Vote-extension heights refuse blocks whose ExtendedCommit is
    missing, height-mismatched, block-mismatched, or lacking extension
    signatures on COMMIT entries (ref: reactor.go:549-553, EnsureExtensions
    at reactor.go:590)."""
    from tendermint_tpu.blocksync.reactor import BlockSyncReactor
    from tendermint_tpu.proto import messages as pb
    from tendermint_tpu.types import BlockID, PartSetHeader
    from tendermint_tpu.types.block import (
        BLOCK_ID_FLAG_ABSENT,
        BLOCK_ID_FLAG_COMMIT,
        BLOCK_ID_FLAG_NIL,
    )

    height = 5
    first_id = BlockID(hash=b"\xaa" * 32, part_set_header=PartSetHeader(total=1, hash=b"\xbb" * 32))
    check = lambda ec: BlockSyncReactor._validate_ext_commit(object(), ec, height, first_id)

    def make_ec(height=height, block_id=first_id, sigs=None):
        if sigs is None:
            sigs = [
                pb.ExtendedCommitSig(
                    block_id_flag=BLOCK_ID_FLAG_COMMIT,
                    validator_address=b"\x01" * 20,
                    timestamp=pb.Timestamp(),
                    signature=b"s" * 64,
                    extension=b"ext",
                    extension_signature=b"e" * 64,
                )
            ]
        return pb.ExtendedCommit(
            height=height, round=0, block_id=block_id.to_proto(), extended_signatures=sigs
        )

    assert check(make_ec()) is None
    assert check(None) is not None  # missing entirely
    assert check(make_ec(height=height + 1)) is not None  # wrong height
    wrong_bid = BlockID(hash=b"\xcc" * 32, part_set_header=PartSetHeader(total=1, hash=b"\xbb" * 32))
    assert check(make_ec(block_id=wrong_bid)) is not None  # wrong block
    no_ext = pb.ExtendedCommitSig(
        block_id_flag=BLOCK_ID_FLAG_COMMIT,
        validator_address=b"\x01" * 20,
        timestamp=pb.Timestamp(),
        signature=b"s" * 64,
    )
    assert check(make_ec(sigs=[no_ext])) is not None  # COMMIT without ext sig
    sneaky_nil = pb.ExtendedCommitSig(
        block_id_flag=BLOCK_ID_FLAG_NIL,
        validator_address=b"\x01" * 20,
        timestamp=pb.Timestamp(),
        signature=b"s" * 64,
        extension=b"bogus",
    )
    assert check(make_ec(sigs=[sneaky_nil])) is not None  # NIL with ext data
    absent = pb.ExtendedCommitSig(block_id_flag=BLOCK_ID_FLAG_ABSENT, timestamp=pb.Timestamp())
    assert check(make_ec(sigs=[make_ec().extended_signatures[0], absent])) is None


def test_validate_ext_commit_cryptographic():
    """Shape-valid but forged extended commits must be rejected before
    persisting: an unverified EC on disk is a poison pill — the next
    restart rebuilds last_commit from it and halts forever."""
    from test_types import _make_validators

    from tendermint_tpu.blocksync.reactor import BlockSyncReactor
    from tendermint_tpu.types import PRECOMMIT, BlockID, PartSetHeader, Vote, VoteSet
    from tendermint_tpu.utils.tmtime import Time

    chain_id = "vec-chain"
    vset, privs = _make_validators(4)
    height, round_ = 5, 0
    block_id = BlockID(hash=b"\xaa" * 32, part_set_header=PartSetHeader(total=1, hash=b"\xbb" * 32))
    vote_set = VoteSet.extended(chain_id, height, round_, PRECOMMIT, vset)
    for i in range(4):
        vote = Vote(
            type=PRECOMMIT,
            height=height,
            round=round_,
            block_id=block_id,
            timestamp=Time.parse_rfc3339("2024-01-02T03:04:05Z"),
            validator_address=vset.validators[i].address,
            validator_index=i,
            extension=b"ext-%d" % i,
        )
        vote.signature = privs[i].sign(vote.sign_bytes(chain_id))
        vote.extension_signature = privs[i].sign(vote.extension_sign_bytes(chain_id))
        vote_set.add_vote(vote)
    ec = vote_set.make_extended_commit()

    check = lambda e: BlockSyncReactor._validate_ext_commit(
        object(), e, height, block_id, vset, chain_id
    )
    assert check(ec) is None  # honest EC verifies

    # an engine/device failure while collecting is not a verdict on the
    # peer: it propagates (the pool routine hands it to on_fatal)
    from tendermint_tpu.crypto.ed25519 import Ed25519BatchVerifier

    def sunk(self):
        def complete():
            raise RuntimeError("UNAVAILABLE: TPU backend setup/compile error")
        return complete

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(Ed25519BatchVerifier, "verify_async", sunk)
        with pytest.raises(RuntimeError, match="UNAVAILABLE"):
            check(ec)

    import copy

    forged = copy.deepcopy(ec)
    sig = bytearray(forged.extended_signatures[1].extension_signature)
    sig[0] ^= 0xFF
    forged.extended_signatures[1].extension_signature = bytes(sig)
    assert check(forged) is not None  # tampered extension signature

    forged = copy.deepcopy(ec)
    sig = bytearray(forged.extended_signatures[2].signature)
    sig[0] ^= 0xFF
    forged.extended_signatures[2].signature = bytes(sig)
    assert check(forged) is not None  # tampered vote signature

    from tendermint_tpu.proto import messages as pb
    from tendermint_tpu.types.block import BLOCK_ID_FLAG_ABSENT

    empty = pb.ExtendedCommit(
        height=height, round=round_, block_id=block_id.to_proto(), extended_signatures=[]
    )
    assert check(empty) is not None  # no power at all

    only_absent = pb.ExtendedCommit(
        height=height, round=round_, block_id=block_id.to_proto(),
        extended_signatures=[
            pb.ExtendedCommitSig(block_id_flag=BLOCK_ID_FLAG_ABSENT, timestamp=pb.Timestamp())
        ] * 4,
    )
    assert check(only_absent) is not None  # slots present, zero power


def test_restart_behind_rejoins_via_blocksync_not_gossip():
    """The restart race (ref: pool.go:189 + the reference's 1s switch
    ticker, reactor.go:466): a node far behind the tip whose FIRST
    status response comes from a stale/height-0 peer must not switch to
    consensus on that view — it must keep blocksyncing once the tip
    peer's status lands. Before the settle-window fix, is_caught_up
    fired on the first check (height 1 >= max_peer_height 0 with one
    stale peer present) and the node crawled to the tip via vote gossip
    instead."""
    keys = make_keys(1)
    gen_doc = make_genesis_doc(keys, CHAIN + "-race")
    gen_doc.consensus_params = fast_params()

    source = make_node(keys, 0, gen_doc)
    source.start()
    try:
        assert wait_for_height([source], 100, timeout=90)
    finally:
        source.stop()
    tip = source.block_store.height()
    assert tip >= 100

    fresh = make_node(keys, 0, gen_doc)  # the restarted/behind node
    stale = make_node(keys, 0, gen_doc)  # a peer with an empty chain

    caught = {}
    done = threading.Event()

    def on_caught_up(state, n):
        caught["n"] = n
        done.set()

    net = MemoryNetwork()
    tip_server = BSNode(net, 0x61, source, block_sync=False)
    stale_server = BSNode(net, 0x62, stale, block_sync=False)
    client = BSNode(net, 0x63, fresh, on_caught_up=on_caught_up)
    for n in (tip_server, stale_server, client):
        n.start()
    try:
        # stale peer's status (height 0) arrives first...
        client.pm.add(Endpoint(protocol="memory", host=stale_server.node_id,
                               node_id=stale_server.node_id))
        time.sleep(0.5)
        assert not done.is_set(), "switched to consensus off a stale height-0 status"
        # ...then the tip peer reports; the node must blocksync to the tip
        client.pm.add(Endpoint(protocol="memory", host=tip_server.node_id,
                               node_id=tip_server.node_id))
        assert done.wait(timeout=120), (
            f"client stuck at {client.reactor.pool.height}, tip {tip}"
        )
    finally:
        for n in (client, tip_server, stale_server):
            n.stop()
    assert caught["n"] >= tip - 2, (
        f"rejoined with only {caught['n']} synced blocks — vote-gossip crawl, not blocksync"
    )
    assert fresh.block_store.height() >= tip - 2


def test_switch_gate_requires_extended_commit():
    """ref: reactor.go:485-507 — a node at a vote-extension height may
    not switch to consensus without the ExtendedCommit its restart
    reconstruction would need: either >= 1 synced block carried one, or
    the store already holds it."""
    import dataclasses

    from test_consensus import make_node
    from tendermint_tpu.types.params import ABCIParams

    keys = make_keys(1)
    gen_doc = make_genesis_doc(keys, CHAIN + "-gate")
    gen_doc.consensus_params = fast_params()
    cs = make_node(keys, 0, gen_doc)

    net = MemoryNetwork()
    bs = BSNode(net, 0x71, cs, block_sync=True)
    r = bs.reactor

    # non-extension chains switch freely
    assert r._can_switch_to_consensus()

    # pretend the synced state sits at an extension height
    r.state = dataclasses.replace(
        r.state,
        last_block_height=7,
        consensus_params=dataclasses.replace(
            r.state.consensus_params, abci=ABCIParams(vote_extensions_enable_height=2)
        ),
    )
    assert not r._can_switch_to_consensus(), "switched without an extended commit"

    # a synced block (which blocksync validates to carry an EC) unblocks
    r.blocks_synced = 1
    assert r._can_switch_to_consensus()

    # ...as does an EC already in the store (initial-height case)
    r.blocks_synced = 0
    from tendermint_tpu.proto import messages as pb

    cs.block_store._db.set(b"EC:" + (7).to_bytes(8, "big"),
                           pb.ExtendedCommit(height=7, round=0).encode())
    assert r._can_switch_to_consensus()


def test_blocksync_then_reconstruct_extended_last_commit():
    """After blocksyncing an extension chain, the node-level switch path
    (rs.last_commit reset + reconstruction, ref SwitchToConsensus
    consensus/reactor.go:256) yields an extensions-verifying last commit
    built from the EC the sync persisted."""
    import dataclasses

    from test_consensus import make_node
    from tendermint_tpu.types.params import ABCIParams

    keys = make_keys(1)
    gen_doc = make_genesis_doc(keys, CHAIN + "-rle")
    gen_doc.consensus_params = dataclasses.replace(
        fast_params(), abci=ABCIParams(vote_extensions_enable_height=2)
    )
    source = make_node(keys, 0, gen_doc)
    source.start()
    try:
        assert wait_for_height([source], 4, timeout=60)
    finally:
        source.stop()
    src_height = source.block_store.height()

    fresh = make_node(keys, 0, gen_doc)
    done = threading.Event()
    result = {}

    def on_caught_up(state, n):
        result["state"], result["n"] = state, n
        done.set()

    net = MemoryNetwork()
    server = BSNode(net, 0x72, source, block_sync=False)
    client = BSNode(net, 0x73, fresh, on_caught_up=on_caught_up)
    server.start()
    client.start()
    try:
        client.pm.add(Endpoint(protocol="memory", host=server.node_id, node_id=server.node_id))
        assert done.wait(timeout=60)
    finally:
        client.stop()
        server.stop()
    assert result["n"] >= src_height - 1  # synced the chain => ECs persisted

    # the node-level switch: rebuild last commit from the synced chain
    state = result["state"]
    fresh.rs.last_commit = None
    fresh._reconstruct_last_commit_if_needed(state)
    lc = fresh.rs.last_commit
    assert lc is not None and lc.extensions_enabled
    assert lc.has_two_thirds_majority()
    assert any(v is not None and v.extension_signature for v in lc.votes)


def test_tampered_block_with_distinct_peers_bans_both():
    """When blocks h and h+1 came from DIFFERENT peers, a verification
    failure must ban BOTH and refetch BOTH heights — either sender
    could be the liar (ref: reactor.go:592-604 errors both)."""
    keys = make_keys(1)
    gen_doc = make_genesis_doc(keys, CHAIN)
    gen_doc.consensus_params = fast_params()
    source = make_node(keys, 0, gen_doc)
    source.start()
    try:
        assert wait_for_height([source], 3, timeout=60)
    finally:
        source.stop()

    fresh = make_node(keys, 0, gen_doc)
    errors = []
    reactor = _stub_reactor(fresh, errors)
    b1 = source.block_store.load_block(1)
    b2 = source.block_store.load_block(2)
    b1.txs = [b"evil"]
    b1.header.data_hash = b"\x99" * 32
    peer1, peer2 = "aa" * 20, "bb" * 20
    reactor.pool.set_peer_range(peer1, 1, 1)
    reactor.pool.set_peer_range(peer2, 2, 3)
    reactor.pool._fill_requests()
    reactor.pool.add_block(peer1, b1)
    reactor.pool.add_block(peer2, b2)
    assert reactor._try_sync_one() is False
    banned = {e.node_id for e in errors}
    assert banned == {peer1, peer2}, banned
    assert peer1 not in reactor.pool.peers
    assert peer2 not in reactor.pool.peers


def test_missing_extended_commit_refetches_at_ve_height():
    """Vote-extension heights REQUIRE the extended commit alongside the
    block; a peer omitting it is re-requested + reported
    (reactor.go:549-553, 590) — without the EC the synced node could
    never serve extension-aware catch-up."""
    import dataclasses

    from tendermint_tpu.types.params import ABCIParams

    keys = make_keys(1)
    gen_doc = make_genesis_doc(keys, CHAIN)
    gen_doc.consensus_params = dataclasses.replace(
        fast_params(), abci=ABCIParams(vote_extensions_enable_height=1)
    )
    source = make_node(keys, 0, gen_doc)
    source.start()
    try:
        assert wait_for_height([source], 3, timeout=60)
    finally:
        source.stop()

    fresh = make_node(keys, 0, gen_doc)
    errors = []
    reactor = _stub_reactor(fresh, errors)
    b1 = source.block_store.load_block(1)
    b2 = source.block_store.load_block(2)
    peer = "cc" * 20
    reactor.pool.set_peer_range(peer, 1, 3)
    reactor.pool._fill_requests()
    # peer serves block 1 WITHOUT its extended commit (ext_commit=None)
    reactor.pool.add_block(peer, b1, ext_commit=None)
    reactor.pool.add_block(peer, b2)
    assert reactor._try_sync_one() is False
    assert errors and errors[0].node_id == peer
    assert fresh.block_store.height() == 0, "block persisted without its EC"
    # the honest EC makes the same blocks sync
    errors.clear()
    ec1 = source.block_store.load_extended_commit_proto(1)
    assert ec1 is not None
    peer2 = "dd" * 20
    reactor.pool.set_peer_range(peer2, 1, 3)
    reactor.pool._fill_requests()
    reactor.pool.add_block(peer2, b1, ext_commit=ec1)
    reactor.pool.add_block(peer2, b2)
    assert reactor._try_sync_one() is True
    assert fresh.block_store.height() == 1


# ---------------------------------------------------- validate before persist


def _fixture_reactor(chain, metrics=None):
    """A joiner of a fixture chain with stub p2p: (reactor, peer errors,
    fatals, the joiner's block store)."""
    from tendermint_tpu.blocksync import fixture

    state, executor, _, block_store = fixture._executor(chain.gen_doc)
    errors, fatal = [], []

    class _Chan:
        def send_to(self, *a, **k):
            return True

        def send_error(self, e):
            errors.append(e)

    class _PM:
        def subscribe(self, cb):
            pass

        def unsubscribe(self, cb):
            pass

    reactor = BlockSyncReactor(state, executor, block_store, _Chan(), _PM(),
                               on_fatal=fatal.append, metrics=metrics)
    return reactor, errors, fatal, block_store


def _samples(counter) -> dict:
    return {tuple(labels.values()): v for _, labels, v in counter.samples()}


def _serve(reactor, blocks_by_peer: dict) -> None:
    """Each peer reports the range of the blocks it holds, is asked for
    them, and delivers."""
    for peer, blocks in blocks_by_peer.items():
        heights = [b.header.height for b in blocks]
        reactor.pool.set_peer_range(peer, min(heights), max(heights))
    for peer, blocks in blocks_by_peer.items():
        for b in blocks:
            reactor.pool.requesters[b.header.height] = peer
            assert reactor.pool.add_block(peer, b)


def _forked_pair(chain, height: int, lie: str):
    """Blocks `height` and `height + 1` as a peer on a fork would serve
    them: block `height` carries a lie in what ValidateBlock checks, and
    EVERY validator has signed it all the same, so that the commit in
    block `height + 1` proves it. `lie`: a LastCommit signature beyond
    the light prefix under the honest header ("tail"), the same with the
    header's LastCommitHash made to match ("tail_rehashed"), or a
    LastCommitHash that matches nothing ("last_commit_hash")."""
    from tendermint_tpu.blocksync import fixture
    from tendermint_tpu.types.block import BlockID

    first = chain.block_store.load_block(height)
    second = chain.block_store.load_block(height + 1)
    if lie == "last_commit_hash":
        first.header.last_commit_hash = b"\x77" * 32
    else:
        cs = first.last_commit.signatures[-1]
        cs.signature = cs.signature[:32] + bytes([cs.signature[32] ^ 1]) + cs.signature[33:]
        if lie == "tail_rehashed":
            first.header.last_commit_hash = first.last_commit.hash()
    first = type(first).decode(first.encode())  # as it comes off the wire: no memo
    forged_id = BlockID(hash=first.hash(), part_set_header=first.make_part_set().header)
    keys_by_addr = {k.pub_key().address(): k for k in chain.keys}
    second.last_commit = fixture.sign_commit(
        chain.chain_id, chain.state.validators, keys_by_addr, height, forged_id,
        second.last_commit.signatures[0].timestamp)
    return first, second


@pytest.mark.parametrize("lie,verdict", [
    ("tail", "wrong Header.LastCommitHash"),
    ("tail_rehashed", "wrong signature (#3)"),
    ("last_commit_hash", "wrong Header.LastCommitHash"),
])
def test_block_that_fails_validation_is_refused_before_it_is_persisted(lie, verdict):
    """A pair the commit check passes (a fork the same validators
    signed) whose first block does not validate against our state: a
    fault of the peers, not of this node. It used to be saved, fail
    inside apply_block and halt the node with its block store one height
    above its state. Now: stage "block", both senders blamed, both
    heights fetched again, nothing persisted, the loop goes on and the
    honest copies sync."""
    from tendermint_tpu.blocksync import fixture
    from tendermint_tpu.metrics import BlockSyncMetrics, Registry

    chain = fixture.build_chain(7, 4, 5)
    metrics = BlockSyncMetrics(Registry())
    reactor, errors, fatal, block_store = _fixture_reactor(chain, metrics)
    first, second = _forked_pair(chain, 2, lie)
    honest, forked, neighbour = "aa" * 20, "bb" * 20, "cc" * 20
    _serve(reactor, {honest: [chain.block_store.load_block(1)], forked: [first],
                     neighbour: [second]})
    assert reactor._try_sync_one() is True  # height 1
    assert reactor._try_sync_one() is False  # the pair (2, 3)
    assert fatal == [] and reactor.sync_error is False
    assert {e.node_id for e in errors} == {forked, neighbour}
    assert all(isinstance(e.err, ValueError) and verdict in str(e.err) for e in errors), errors
    assert block_store.height() == 1 and reactor.state.last_block_height == 1
    assert reactor.pool.height == 2
    assert forked not in reactor.pool.peers and neighbour not in reactor.pool.peers
    assert 2 not in reactor.pool.requesters and 3 not in reactor.pool.requesters
    assert _samples(metrics.refusals) == {("block",): 1.0}
    assert _samples(metrics.refusal_seconds)[()] > 0
    assert reactor.pool.blocks_dropped == 2 and _samples(metrics.blocks_dropped)[()] == 2.0
    # the honest copies of the same heights go through
    errors.clear()
    _serve(reactor, {honest: [chain.block_store.load_block(h) for h in (2, 3, 4)]})
    assert reactor._try_sync_one() is True and reactor._try_sync_one() is True
    assert errors == [] and block_store.height() == 3 == reactor.state.last_block_height
    assert block_store.load_block(2).hash() == chain.block_hashes[1]


def test_last_commit_lie_beyond_the_light_prefix_is_a_peer_fault():
    """One signature of block 3's LastCommit corrupted in the row the
    light rule never reads (3 of 4 equal validators are enough). The
    pair (2, 3) passes, and the pair (3, 4) is refused at the commit:
    the part set the joiner makes of the bytes served is not the one
    block 4's LastCommit signed. Both senders blamed, nothing above the
    state saved, the heights asked for again, the node not halted."""
    from tendermint_tpu.blocksync import fixture
    from tendermint_tpu.metrics import BlockSyncMetrics, Registry

    chain = fixture.build_chain(8, 4, 5)
    served = fixture.corrupted_copy(chain, 2, 3)
    metrics = BlockSyncMetrics(Registry())
    reactor, errors, fatal, block_store = _fixture_reactor(chain, metrics)
    honest, liar = "aa" * 20, "bb" * 20
    _serve(reactor, {honest: [chain.block_store.load_block(h) for h in (1, 2, 4, 5)],
                     liar: [served.load_block(3)]})
    assert reactor._try_sync_one() is True and reactor._try_sync_one() is True
    assert reactor._try_sync_one() is False
    assert fatal == [] and {e.node_id for e in errors} == {honest, liar}
    assert all("wrong block ID" in str(e.err) for e in errors)
    assert block_store.height() == 2 == reactor.state.last_block_height
    assert reactor.pool.peers == {} and reactor.pool.blocks == {}
    assert not {3, 4, 5} & set(reactor.pool.requesters)
    assert _samples(metrics.refusals) == {("commit",): 1.0}
    # blocks 3, 4 and 5 were received and are gone with their senders
    assert _samples(metrics.blocks_received)[()] == 5.0
    assert _samples(metrics.blocks_dropped)[()] == 3.0


def test_device_failure_inside_validate_block_is_fatal_not_a_lying_peer(monkeypatch):
    """The validation before save_block blames peers for a verdict
    alone: a device or runtime error inside it halts the node, bans
    nobody, persists nothing."""
    from tendermint_tpu.blocksync import fixture

    chain = fixture.build_chain(5, 4, 3)
    reactor, errors, fatal, block_store = _fixture_reactor(chain)
    peer = "cc" * 20
    _serve(reactor, {peer: [chain.block_store.load_block(h) for h in (1, 2, 3)]})

    class XlaRuntimeError(RuntimeError):
        pass

    def broken_device(state, block):
        raise XlaRuntimeError("INTERNAL: core halted unexpectedly")

    monkeypatch.setattr(reactor.block_exec, "validate_block", broken_device)
    reactor._pool_routine()  # returns by itself: the fatal path ends the loop
    assert len(fatal) == 1 and isinstance(fatal[0], XlaRuntimeError)
    assert reactor.sync_error is True
    assert errors == [] and peer in reactor.pool.peers
    assert reactor.pool.height == 1 and block_store.height() == 0


def test_pool_counts_dropped_blocks_and_a_refused_peers_return():
    from tendermint_tpu.metrics import BlockSyncMetrics, Registry

    class FakeBlock:
        def __init__(self, h):
            self.header = type("H", (), {"height": h})()

    metrics = BlockSyncMetrics(Registry())
    pool = BlockPool(1, lambda h, p: None, metrics=metrics)
    refused, other = "aa" * 20, "bb" * 20
    pool.set_peer_range(refused, 1, 6)
    pool._fill_requests()
    for h in range(1, 5):
        assert pool.add_block(refused, FakeBlock(h))
    pool.set_peer_range(other, 1, 6)
    assert _samples(metrics.blocks_received)[()] == 4.0
    assert pool.redo_request(1) == refused
    assert pool.blocks == {} and pool.blocks_dropped == 4
    assert _samples(metrics.blocks_dropped)[()] == 4.0
    # a peer that merely disconnects takes its blocks with it, and is no return
    pool._fill_requests()
    assert pool.add_block(other, FakeBlock(1))
    pool.remove_peer(other)
    assert pool.blocks_dropped == 5
    pool.set_peer_range(other, 1, 6)
    assert _samples(metrics.peer_returns) == {}
    # the refused peer's next status brings it back, and is counted once
    pool.set_peer_range(refused, 1, 6)
    pool.set_peer_range(refused, 1, 6)
    assert refused in pool.peers
    assert _samples(metrics.peer_returns)[()] == 1.0
    assert _samples(metrics.peer_out_seconds)[()] >= 0.0


def test_verify_ahead_is_counted_used_or_stale():
    """A launch dispatched one height ahead is used by the next
    iteration, or found stale because a refusal took its blocks away."""
    from tendermint_tpu.blocksync import fixture
    from tendermint_tpu.metrics import BlockSyncMetrics, Registry

    chain = fixture.build_chain(9, 4, 6)
    metrics = BlockSyncMetrics(Registry())
    reactor, errors, fatal, _ = _fixture_reactor(chain, metrics)
    honest, forked = "aa" * 20, "bb" * 20
    first, second = _forked_pair(chain, 2, "tail")
    _serve(reactor, {honest: [chain.block_store.load_block(h) for h in (1, 4, 5, 6)],
                     forked: [first, second]})
    assert reactor._try_sync_one() is True  # 1 applied, 2 dispatched ahead
    assert reactor._try_sync_one() is False  # the ahead is used; 3's goes out; 2 fails validation
    assert _samples(metrics.verify_ahead) == {("used",): 1.0}
    _serve(reactor, {honest: [chain.block_store.load_block(h) for h in (2, 3)]})
    assert reactor._try_sync_one() is True  # the launch for the forked 3 is stale
    assert _samples(metrics.verify_ahead) == {("used",): 1.0, ("stale",): 1.0}
    assert fatal == [] and {e.node_id for e in errors} == {forked}


def test_a_refusal_is_a_span_under_the_iteration_that_made_it():
    """`blocksync.refuse` (height, stage, banned, dropped) under a
    `blocksync.try_sync` that says `refused`; `blocksync.validate` before
    `blocksync.save_block`; `blocksync.peer_out`, in hindsight, when a
    refused peer's status brings it back."""
    from tendermint_tpu import trace as T
    from tendermint_tpu.blocksync import fixture

    chain = fixture.build_chain(7, 4, 5)
    reactor, errors, fatal, _ = _fixture_reactor(chain)
    first, second = _forked_pair(chain, 2, "tail")
    honest, forked = "aa" * 20, "bb" * 20
    _serve(reactor, {honest: [chain.block_store.load_block(1)], forked: [first, second]})
    was = T.enabled()
    T.set_enabled(True)
    T.clear()
    try:
        assert reactor._try_sync_one() is True and reactor._try_sync_one() is False
        reactor.pool.set_peer_range(forked, 1, 5)
        events = [ev for ev in T.export()["traceEvents"] if ev.get("ph") == "X"]
    finally:
        T.set_enabled(was)
        T.clear()
    by_name = {}
    for ev in events:
        by_name.setdefault(ev["name"], []).append(ev)
    applied, refused = by_name["blocksync.try_sync"]
    assert applied["args"]["applied"] is True and applied["args"]["refused"] is False
    assert refused["args"]["applied"] is False and refused["args"]["refused"] is True
    (refuse,) = by_name["blocksync.refuse"]
    assert refuse["args"]["parent"] == refused["args"]["span"]
    assert {k: refuse["args"][k] for k in ("height", "stage", "banned", "dropped")} == {
        "height": 2, "stage": "block", "banned": 1, "dropped": 2}
    validated = [ev["args"]["height"] for ev in by_name["blocksync.validate"]]
    assert validated == [1, 2]
    assert [ev["args"]["height"] for ev in by_name["blocksync.save_block"]] == [1]
    (out,) = by_name["blocksync.peer_out"]
    assert out["args"]["peer"] == forked and out["ts"] >= refuse["ts"] and out["dur"] >= 0
    assert fatal == [] and {e.node_id for e in errors} == {forked}
