"""A seeded chain whose validator set rotates, built without consensus.

`benchmark/chain.py`'s builder with one thing added, the
configuration's `rotation`: every block carries, beside its kvstore
txs, a `val:<key>!0` for the longest-serving validator and a
`val:<key>!<power>` for a key never seen, and the program's normal path
does the rest (the kvstore's validator updates, `BlockExecutor`,
`State.update`'s H + 2 rule, `StateStore.save`). Each commit is signed
by the set the program's state holds for that height, in its order.

Beside the program's stores the chain keeps, in plain values, the set
the program gave every height; `benchmark/reference_churn.py` writes
the same schedule from the rotation rule alone, and the two are
compared by hash.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from benchmark import reference as ref
from benchmark.chain import GENESIS_UNIX_NS, PART_SIZE, Chain, key_seeds


@dataclass
class ChurnChain(Chain):
    key_pubkeys: list[bytes] = field(default_factory=list)  # every key of the seed, by index
    # index h-1: the set that signs height h as the program's state held it,
    # (pubkey, power) in validator-set order
    sets: list[list[tuple[bytes, int]]] = field(default_factory=list)


def build(config: dict, seed: int) -> ChurnChain:
    """`validators` equal-power ed25519 validators at genesis; block H
    retires key H - 1 and admits key validators + H - 1 (times
    `rotation.validators_per_block`); every validator of a height's set
    signs its commit at round 0."""
    from tendermint_tpu.abci import LocalClient
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.abci.kvstore import KVStoreApplication, make_validator_tx
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
    per_block = config["rotation"]["validators_per_block"]
    seeds = key_seeds(seed, n + n_blocks * per_block)
    if ref.signer(seeds[0])(b"probe") != ref.sign_plain(seeds[0], b"probe"):
        raise RuntimeError("the fast signer and RFC 8032 signing disagree")
    pubkeys = [ref.public_key(key_seed) for key_seed in seeds]
    signers = {Ed25519PubKey(pk).address(): ref.signer(key_seed)
               for pk, key_seed in zip(pubkeys, seeds)}
    genesis = [GenesisValidator(address=Ed25519PubKey(pk).address(), pub_key=Ed25519PubKey(pk),
                                power=power, name=f"v{i}") for i, pk in enumerate(pubkeys[:n])]
    gen_doc = GenesisDoc(chain_id=chain_id, genesis_time=Time.from_unix_ns(GENESIS_UNIX_NS),
                         validators=genesis)
    state = make_genesis_state(gen_doc)
    state_store, block_store = StateStore(MemDB()), BlockStore(MemDB())
    state_store.save(state)
    app = KVStoreApplication()
    app.init_chain(abci.RequestInitChain(chain_id=chain_id, validators=[
        abci.ValidatorUpdate(pub_key_bytes=pk, power=power) for pk in pubkeys[:n]]))
    executor = SourceExecutor(state_store, LocalClient(app), block_store=block_store)
    chain = ChurnChain(chain_id, gen_doc, state, state_store, block_store, state.validators,
                       txs_per_block=n_txs, key_pubkeys=pubkeys)
    last_commit = Commit(height=0)
    for height in range(1, n_blocks + 1):
        vals = state.validators  # the set that signs this height
        chain.sets.append([(v.pub_key.bytes(), v.voting_power) for v in vals.validators])
        time_ns = GENESIS_UNIX_NS + height * 10**9
        time = Time.from_unix_ns(time_ns)
        txs = [b"s%d-h%d-t%d=%d" % (seed, height, t, height * 1000 + t) for t in range(n_txs)]
        for k in range((height - 1) * per_block, height * per_block):
            txs.append(make_validator_tx(pubkeys[k], 0))
            txs.append(make_validator_tx(pubkeys[n + k], power))
        block = state.make_block(height, txs, last_commit, [], vals.get_proposer().address, time)
        parts = PartSet.from_data(block.to_proto().encode(), PART_SIZE)
        block_id = BlockID(hash=block.hash(), part_set_header=parts.header)
        state = executor.apply_block(state, block_id, block)
        msg = ref.vote_sign_bytes(chain_id, height, 0, block_id.hash, parts.header.total,
                                  parts.header.hash, time_ns)
        last_commit = Commit(height=height, round=0, block_id=block_id, signatures=[
            CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, time, signers[v.address](msg))
            for v in vals.validators])
        block_store.save_block(block, parts, last_commit)
        chain.block_hashes.append(block_id.hash)
        chain.times_ns.append(time_ns)
    chain.state = state
    return chain
