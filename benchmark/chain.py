"""Seeded chains for the benchmark's cells, built without consensus.

The benchmark's own copy of `tendermint_tpu/blocksync/fixture.py`
`build_chain` (PERF.md, Open questions, lists the original for a later
PR), with three differences. Keys and signatures are the reference's
(`benchmark/reference.py`): every commit is signed over sign-bytes the
reference encoded, with OpenSSL where the `cryptography` package
imports (~30 us a signature against ~2 ms in pure Python), so a
window-long 1000-validator chain is affordable and the program is held
to the wire format, not to itself. The source does not re-verify the
commits it has just signed while it builds (a joiner does, in warm-up
and in the window). And beside the program's stores, which the serving
peer answers from, the chain keeps a record in plain values that the
reference is judged against.

Everything is a function of (configuration, seed): keys, txs and
timestamps, hence every block hash and the app hash.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field

from benchmark import reference as ref

GENESIS_UNIX_NS = 1_700_000_000 * 10**9
PART_SIZE = 65536


@dataclass
class Chain:
    """The program's side (what the peer serves, what a joiner starts
    from) and the reference's side (plain values)."""

    chain_id: str
    gen_doc: object
    state: object  # after the last block
    state_store: object
    block_store: object
    validators: object  # the one ValidatorSet of the chain
    # plain values, for the reference
    pubkeys: list[bytes] = field(default_factory=list)  # in validator-set order
    powers: list[int] = field(default_factory=list)
    block_hashes: list[bytes] = field(default_factory=list)  # index h-1, as built
    times_ns: list[int] = field(default_factory=list)
    txs_per_block: int = 0

    @property
    def height(self) -> int:
        return len(self.block_hashes)


def key_seeds(seed: int, n: int) -> list[bytes]:
    return [hashlib.sha256(b"bench-key:%d:%d" % (seed, i)).digest() for i in range(n)]


def build(config: dict, seed: int) -> Chain:
    """The configuration's chain: `validators` equal-power ed25519
    validators, `blocks` blocks of `txs_per_block` kvstore txs, every
    validator signing every commit at round 0."""
    from tendermint_tpu.abci import LocalClient
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
    from tendermint_tpu.state import BlockExecutor, StateStore, make_genesis_state
    from tendermint_tpu.store.blockstore import BlockStore
    from tendermint_tpu.store.kv import MemDB
    from tendermint_tpu.types.block import BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.part_set import PartSet
    from tendermint_tpu.utils.tmtime import Time

    class SourceExecutor(BlockExecutor):
        """The node that made the block and signed its commit itself."""

        def validate_block(self, state, block) -> None:
            return None

    n, n_blocks, n_txs = config["validators"], config["blocks"], config["txs_per_block"]
    chain_id, power = config["chain_id"], config["voting_power"]
    seeds = key_seeds(seed, n)
    if ref.signer(seeds[0])(b"probe") != ref.sign_plain(seeds[0], b"probe"):
        raise RuntimeError("the fast signer and RFC 8032 signing disagree")
    signers = {}
    validators = []
    for i, key_seed in enumerate(seeds):
        pub = Ed25519PubKey(ref.public_key(key_seed))
        signers[pub.address()] = ref.signer(key_seed)
        validators.append(GenesisValidator(address=pub.address(), pub_key=pub, power=power,
                                           name=f"v{i}"))
    gen_doc = GenesisDoc(chain_id=chain_id, genesis_time=Time.from_unix_ns(GENESIS_UNIX_NS),
                         validators=validators)
    state = make_genesis_state(gen_doc)
    state_store, block_store = StateStore(MemDB()), BlockStore(MemDB())
    state_store.save(state)
    executor = SourceExecutor(state_store, LocalClient(KVStoreApplication()),
                              block_store=block_store)
    vals = state.validators
    chain = Chain(chain_id, gen_doc, state, state_store, block_store, vals,
                  pubkeys=[v.pub_key.bytes() for v in vals.validators],
                  powers=[v.voting_power for v in vals.validators], txs_per_block=n_txs)
    order = [(v.address, signers[v.address]) for v in vals.validators]
    last_commit = Commit(height=0)
    for height in range(1, n_blocks + 1):
        time_ns = GENESIS_UNIX_NS + height * 10**9
        time = Time.from_unix_ns(time_ns)
        txs = [b"s%d-h%d-t%d=%d" % (seed, height, t, height * 1000 + t) for t in range(n_txs)]
        block = state.make_block(height, txs, last_commit, [],
                                 state.validators.get_proposer().address, time)
        parts = PartSet.from_data(block.to_proto().encode(), PART_SIZE)
        block_id = BlockID(hash=block.hash(), part_set_header=parts.header)
        state = executor.apply_block(state, block_id, block)
        msg = ref.vote_sign_bytes(chain_id, height, 0, block_id.hash, parts.header.total,
                                  parts.header.hash, time_ns)
        last_commit = Commit(height=height, round=0, block_id=block_id, signatures=[
            CommitSig(BLOCK_ID_FLAG_COMMIT, address, time, sign(msg)) for address, sign in order
        ])
        block_store.save_block(block, parts, last_commit)
        chain.block_hashes.append(block_id.hash)
        chain.times_ns.append(time_ns)
    chain.state = state
    return chain


def corrupted_store(chain: Chain, commit_height: int, sig_index: int):
    """The chain's blocks up to commit_height + 2 in a new store, with
    the lowest bit of s flipped in signature sig_index of the commit for
    commit_height, as block commit_height + 1 carries it: what a lying
    peer serves. s stays below L, so only the curve equation refuses it."""
    from tendermint_tpu.store.blockstore import BlockStore
    from tendermint_tpu.store.kv import MemDB
    from tendermint_tpu.types.part_set import PartSet

    store = BlockStore(MemDB())
    for height in range(1, min(chain.height, commit_height + 2) + 1):
        block = chain.block_store.load_block(height)
        if height == commit_height + 1:
            cs = block.last_commit.signatures[sig_index]
            cs.signature = flip_s(cs.signature)
        parts = PartSet.from_data(block.to_proto().encode(), PART_SIZE)
        store.save_block(block, parts, chain.block_store.load_seen_commit(height))
    return store


def signing_prefix(chain: Chain, num: int, den: int) -> int:
    """How many signatures, in validator order, a check that stops once
    more than num/den of the power has signed walks through."""
    needed, tallied = sum(chain.powers) * num // den, 0
    for i, power in enumerate(chain.powers):
        tallied += power
        if tallied > needed:
            return i + 1
    raise ValueError("the whole set does not hold that share")


def flip_s(sig: bytes) -> bytes:
    return sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]


def header_values(header) -> dict:
    """A program Header as the plain values `reference.header_hash` takes."""
    lb = header.last_block_id
    return dict(
        version_block=header.version_block, version_app=header.version_app,
        chain_id=header.chain_id, height=header.height, time_ns=header.time.unix_ns(),
        last_block_id=dict(hash=lb.hash, parts_total=lb.part_set_header.total,
                           parts_hash=lb.part_set_header.hash),
        last_commit_hash=header.last_commit_hash, data_hash=header.data_hash,
        validators_hash=header.validators_hash,
        next_validators_hash=header.next_validators_hash,
        consensus_hash=header.consensus_hash, app_hash=header.app_hash,
        last_results_hash=header.last_results_hash, evidence_hash=header.evidence_hash,
        proposer_address=header.proposer_address,
    )


def commit_values(chain: Chain, commit) -> tuple[list, list]:
    """A program Commit as (signatures, messages) in validator order for
    `reference.commit_verdict`: the bytes are the commit's, the
    sign-bytes are encoded by the reference from the commit's own block
    id and timestamps."""
    bid = commit.block_id
    sigs, msgs = [], []
    for cs in commit.signatures:
        committed = cs.block_id_flag == 2
        sigs.append(cs.signature if committed else None)
        msgs.append(ref.vote_sign_bytes(
            chain.chain_id, commit.height, commit.round, bid.hash,
            bid.part_set_header.total, bid.part_set_header.hash, cs.timestamp.unix_ns(),
        ) if committed else b"")
    return sigs, msgs
