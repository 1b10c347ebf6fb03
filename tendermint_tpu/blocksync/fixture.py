"""Seeded chains built without consensus, and an in-process blocksync of
them — the fixture chip_smoke.py and the blocksync cells of the
benchmark (ROADMAP B1) drive.

A chain is made the way a proposer and its validators would make it,
minus the gossip: `state.make_block` -> `BlockExecutor.apply_block` ->
a seen-commit signed by every validator -> `BlockStore.save_block`.
Applying height h validates its LastCommit, so building the chain
already runs `verify_commit` at the full validator count through the
verification engine. Everything is a function of `seed`: keys, txs and
timestamps, hence every block hash and the app hash.

`sync` then joins a node with empty stores to a serving peer over
`p2p.MemoryNetwork` through the real `BlockPool`, `BlockSyncReactor`
(verify-ahead included), `apply_block` and stores.
"""

from __future__ import annotations

import hashlib
import threading
from dataclasses import dataclass, field

from ..abci import LocalClient
from ..abci.kvstore import KVStoreApplication
from ..crypto.ed25519 import Ed25519PrivKey
from ..p2p import (
    MemoryNetwork,
    NodeInfo,
    PeerManager,
    PeerManagerOptions,
    Router,
    node_id_from_pubkey,
)
from ..p2p.transport import Endpoint
from ..state import BlockExecutor, State, StateStore, make_genesis_state
from ..store.blockstore import BlockStore
from ..store.kv import MemDB
from ..types.block import BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig
from ..types.genesis import GenesisDoc, GenesisValidator
from ..types.part_set import PartSet
from ..types.validator_set import ValidatorSet
from ..types.vote import PRECOMMIT, Vote
from ..utils.tmtime import Time
from .reactor import BlockSyncReactor, blocksync_channel_descriptor

GENESIS_UNIX_NS = 1_700_000_000 * 10**9
PART_SIZE = 65536


def validator_keys(seed: int, n: int) -> list[Ed25519PrivKey]:
    """n ed25519 keys, each from its own 32-byte seed derived from
    (seed, index)."""
    return [
        Ed25519PrivKey.generate(hashlib.sha256(b"tm-fixture-key:%d:%d" % (seed, i)).digest())
        for i in range(n)
    ]


def sign_commit(chain_id: str, vals: ValidatorSet, keys_by_addr: dict, height: int,
                block_id: BlockID, time: Time) -> Commit:
    """Every validator precommits block_id at round 0 (ref:
    types/test_util.go makeCommit)."""
    sigs = []
    for idx, val in enumerate(vals.validators):
        vote = Vote(
            type=PRECOMMIT, height=height, round=0, block_id=block_id, timestamp=time,
            validator_address=val.address, validator_index=idx,
        )
        sig = keys_by_addr[val.address].sign(vote.sign_bytes(chain_id))
        sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, val.address, time, sig))
    return Commit(height=height, round=0, block_id=block_id, signatures=sigs)


@dataclass
class Chain:
    """A built chain: the stores a serving peer answers from, and what a
    joiner must arrive at."""

    gen_doc: GenesisDoc
    keys: list[Ed25519PrivKey]
    state: State  # after the last block
    state_store: StateStore
    block_store: BlockStore
    block_hashes: list[bytes] = field(default_factory=list)  # index h-1
    app_hashes: list[bytes] = field(default_factory=list)  # state.app_hash after height h

    @property
    def chain_id(self) -> str:
        return self.gen_doc.chain_id

    @property
    def height(self) -> int:
        return len(self.block_hashes)

    def commit_jobs(self, commit: Commit) -> list[tuple]:
        """(pub_key, sign_bytes, signature) for every signature of a
        commit of this chain, whose validator set never changes."""
        vals = self.state.validators.validators
        return [(vals[i].pub_key, commit.vote_sign_bytes(self.chain_id, i), cs.signature)
                for i, cs in enumerate(commit.signatures)]


def _executor(gen_doc: GenesisDoc) -> tuple[State, BlockExecutor, StateStore, BlockStore]:
    state = make_genesis_state(gen_doc)
    state_store, block_store = StateStore(MemDB()), BlockStore(MemDB())
    state_store.save(state)
    executor = BlockExecutor(state_store, LocalClient(KVStoreApplication()), block_store=block_store)
    return state, executor, state_store, block_store


def build_chain(seed: int, n_vals: int, n_blocks: int, txs_per_block: int = 4,
                chain_id: str = "fixture-chain") -> Chain:
    """A chain of n_blocks blocks signed by n_vals equal-power ed25519
    validators, each block carrying txs_per_block kvstore txs. Costs
    n_vals * n_blocks pure-Python signatures (~2 ms each)."""
    keys = validator_keys(seed, n_vals)
    gen_doc = GenesisDoc(
        chain_id=chain_id,
        genesis_time=Time.from_unix_ns(GENESIS_UNIX_NS),
        validators=[
            GenesisValidator(address=k.pub_key().address(), pub_key=k.pub_key(), power=10,
                             name=f"v{i}")
            for i, k in enumerate(keys)
        ],
    )
    keys_by_addr = {k.pub_key().address(): k for k in keys}
    state, executor, state_store, block_store = _executor(gen_doc)
    chain = Chain(gen_doc, keys, state, state_store, block_store)
    last_commit = Commit(height=0)
    for height in range(1, n_blocks + 1):
        time = Time.from_unix_ns(GENESIS_UNIX_NS + height * 10**9)
        txs = [b"s%d-h%d-t%d=%d" % (seed, height, t, height * 1000 + t)
               for t in range(txs_per_block)]
        proposer = state.validators.get_proposer()
        block = state.make_block(height, txs, last_commit, [], proposer.address, time)
        parts = PartSet.from_data(block.to_proto().encode(), PART_SIZE)
        block_id = BlockID(hash=block.hash(), part_set_header=parts.header)
        state = executor.apply_block(state, block_id, block)
        last_commit = sign_commit(chain_id, state.last_validators, keys_by_addr, height,
                                  block_id, time)
        block_store.save_block(block, parts, last_commit)
        chain.block_hashes.append(block.hash())
        chain.app_hashes.append(state.app_hash)
    chain.state = state
    return chain


def corrupted_copy(chain: Chain, commit_height: int, sig_index: int) -> BlockStore:
    """The chain's blocks in a new store, with one bit flipped in
    signature sig_index of the commit for commit_height as block
    commit_height + 1 carries it — what a lying peer would serve. The
    bit is the lowest of s, which stays below L: the signature passes
    every host precheck and only the curve equation refuses it."""
    store = BlockStore(MemDB())
    for height in range(1, chain.height + 1):
        block = chain.block_store.load_block(height)
        if height == commit_height + 1:
            cs = block.last_commit.signatures[sig_index]
            cs.signature = cs.signature[:32] + bytes([cs.signature[32] ^ 1]) + cs.signature[33:]
        parts = PartSet.from_data(block.to_proto().encode(), PART_SIZE)
        store.save_block(block, parts, chain.block_store.load_seen_commit(height))
    return store


class _Peer:
    """One end of the in-process network, carrying only the blocksync
    reactor (the harness of tests/test_blocksync.py)."""

    def __init__(self, network, key_seed: bytes, chain_id: str, state, block_exec,
                 block_store, **reactor_kw):
        key = Ed25519PrivKey.generate(hashlib.sha256(key_seed).digest())
        self.node_id = node_id_from_pubkey(key.pub_key())
        self.pm = PeerManager(self.node_id, PeerManagerOptions(max_connected=8))
        self.router = Router(
            NodeInfo(node_id=self.node_id, network=chain_id), key, self.pm,
            [network.create_transport(self.node_id)],
        )
        self.channel = self.router.open_channel(blocksync_channel_descriptor())
        self.reactor = BlockSyncReactor(
            state, block_exec, block_store, self.channel, self.pm, **reactor_kw
        )

    def start(self) -> None:
        self.router.start()
        self.reactor.start()

    def stop(self) -> None:
        self.reactor.stop()
        self.router.stop()


@dataclass
class SyncResult:
    state: State  # the joiner's state when the sync ended
    block_store: BlockStore  # the joiner's
    blocks_synced: int
    caught_up: bool
    fatal: BaseException | None  # what on_fatal was handed, if anything
    peer_errors: list  # PeerErrors the joiner raised against its peer


def sync(chain: Chain, serve_from: BlockStore | None = None, timeout: float = 600.0,
         until_peer_error: bool = False) -> SyncResult:
    """Join a node with empty stores to a peer serving `serve_from`
    (default: the chain's own store) and block-sync until it catches
    up, halts through on_fatal, raises an error against its peer (with
    until_peer_error) or `timeout` seconds pass. Blocksync proves block
    h with block h+1's LastCommit, so a caught-up joiner holds
    chain.height - 1 blocks."""
    state, executor, _state_store, block_store = _executor(chain.gen_doc)
    done, caught_up = threading.Event(), threading.Event()
    fatal: list = []
    peer_errors: list = []

    def on_caught_up(_state, _n):
        caught_up.set()
        done.set()

    def on_fatal(exc):
        fatal.append(exc)
        done.set()

    net = MemoryNetwork()
    source_exec = BlockExecutor(chain.state_store, LocalClient(KVStoreApplication()))
    server = _Peer(net, b"fixture-server", chain.chain_id, chain.state, source_exec,
                   chain.block_store if serve_from is None else serve_from, block_sync=False)
    joiner = _Peer(net, b"fixture-joiner", chain.chain_id, state, executor, block_store,
                   on_caught_up=on_caught_up, on_fatal=on_fatal)
    send_error = joiner.channel.send_error

    def record_error(peer_error):
        peer_errors.append(peer_error)
        send_error(peer_error)
        if until_peer_error:
            done.set()

    joiner.channel.send_error = record_error
    server.start()
    joiner.start()
    try:
        joiner.pm.add(Endpoint(protocol="memory", host=server.node_id, node_id=server.node_id))
        done.wait(timeout)
    finally:
        joiner.stop()
        server.stop()
    reactor = joiner.reactor
    return SyncResult(reactor.state, block_store, reactor.blocks_synced, caught_up.is_set(),
                      fatal[0] if fatal else None, peer_errors)
