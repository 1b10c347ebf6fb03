"""The store a lying peer serves: `benchmark/chain.py`'s honest chain
with the lies of `benchmark/reference_badpeer.py`'s schedule written
into it, one signature of a block's LastCommit with the lowest bit of s
flipped (`chain.flip_s`: s stays below L, so only the curve equation
refuses it). The header is left as it was: the liar cannot change what
2/3 of the validators signed. Every block is decoded from the honest
store and encoded again into the liar's, so a block that carries a lie
is stored under the part set of the bytes the liar serves.
"""

from __future__ import annotations

from benchmark import chain as chainlib


def liar_store(chain: chainlib.Chain, lies):
    """A new block store holding the whole chain as the liar serves it.
    `lies` have `.height` (the block) and `.row` (the signature of its
    LastCommit, in validator-set order)."""
    from tendermint_tpu.store.blockstore import BlockStore
    from tendermint_tpu.store.kv import MemDB
    from tendermint_tpu.types.part_set import PartSet

    rows = {lie.height: lie.row for lie in lies}
    store = BlockStore(MemDB())
    for height in range(1, chain.height + 1):
        block = chain.block_store.load_block(height)
        if height in rows:
            cs = block.last_commit.signatures[rows[height]]
            cs.signature = chainlib.flip_s(cs.signature)
        parts = PartSet.from_data(block.to_proto().encode(), chainlib.PART_SIZE)
        store.save_block(block, parts, chain.block_store.load_seen_commit(height))
    return store
