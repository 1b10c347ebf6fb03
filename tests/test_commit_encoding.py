"""A commit's encoding, made once and kept (types/block.py Commit._encoding).

`Commit.encode()`, `Commit.hash()`, `Block.encode()` and the part set's
header must be what the `to_proto()` path gives, byte for byte, for
every shape of commit; a change to the commit or to any of its slots,
in place or by replacement, must give fresh bytes; and the block store
must write what it wrote when it encoded every record anew.
"""

import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tendermint_tpu.crypto.merkle import hash_from_byte_slices
from tendermint_tpu.metrics import hash_metrics
from tendermint_tpu.store.blockstore import KEY_COMMIT, KEY_META, KEY_SEEN_COMMIT, BlockMeta, BlockStore, _h
from tendermint_tpu.store.kv import MemDB
from tendermint_tpu.types.block import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    BLOCK_PART_SIZE_BYTES,
    Block,
    BlockID,
    Commit,
    CommitSig,
    Header,
    PartSetHeader,
    commit_reads,
)
from tendermint_tpu.types.part_set import PartSet
from tendermint_tpu.utils.tmtime import Time


def _sig(rng: random.Random, flag: int) -> CommitSig:
    if flag == BLOCK_ID_FLAG_ABSENT:
        return CommitSig.new_absent()
    return CommitSig(flag, rng.randbytes(20), Time(1_700_000_000 + rng.randrange(10**6),
                                                   rng.randrange(10**9)), rng.randbytes(64))


def _commit(n: int, flags=(BLOCK_ID_FLAG_COMMIT,), height: int = 9, seed: int = 1) -> Commit:
    rng = random.Random(seed)
    return Commit(height=height, round=1,
                  block_id=BlockID(rng.randbytes(32), PartSetHeader(2, rng.randbytes(32))),
                  signatures=[_sig(rng, flags[i % len(flags)]) for i in range(n)])


def _block(last_commit: Commit | None, height: int = 10) -> Block:
    header = Header(chain_id="kept", height=height, time=Time(1_700_000_000, 5),
                    validators_hash=b"\x03" * 32, next_validators_hash=b"\x04" * 32,
                    consensus_hash=b"\x05" * 32, app_hash=b"\x06" * 32,
                    proposer_address=b"\x07" * 20)
    return Block(header=header, txs=[b"a=1", b"b=2"], last_commit=last_commit)


MIXED = (BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_NIL)
SHAPES = {
    "commit_absent_nil": lambda: _block(_commit(7, MIXED)),
    "empty_commit_at_height_1": lambda: _block(Commit(), height=1),
    "nil_last_commit": lambda: _block(None),
    "4_signatures": lambda: _block(_commit(4)),
    "1000_signatures": lambda: _block(_commit(1000, MIXED)),
    "10000_signatures": lambda: _block(_commit(10000)),
}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_kept_bytes_are_the_proto_paths(shape):
    block = SHAPES[shape]()
    commit = block.last_commit
    # the proto path first, on a copy that keeps nothing
    want_block = Block.from_proto(block.to_proto()).to_proto().encode()
    assert block.to_proto().encode() == want_block
    if commit is not None:
        want = commit.to_proto().encode()
        leaves = [cs.to_proto().encode() for cs in commit.signatures]
        assert commit.encode() == want
        assert commit.hash() == hash_from_byte_slices(leaves)
        assert commit.encode() == want and commit.hash() == hash_from_byte_slices(leaves)
    assert block.encode() == want_block
    ps = block.make_part_set(part_size=4096)
    assert ps.header == PartSet.from_data(want_block, 4096).header
    assert ps.source_block is block and ps.byte_size == len(want_block)
    arrived = PartSet.from_data(want_block, 4096)  # parts the block did not make: encoded
    assert block.encoded_size(ps) == block.encoded_size(arrived) == len(want_block)


def _kept_encode(commit: Commit):
    return commit.encode(), commit.hash()


def _in_place(c):
    c.signatures[1].signature = b"\x01" * 64


def _timestamp(c):
    c.signatures[0].timestamp = Time(3, 4)


def _flag(c):
    c.signatures[2].block_id_flag = BLOCK_ID_FLAG_NIL


def _round(c):
    c.round = 7


def _block_id(c):
    c.block_id = BlockID(b"\x09" * 32, PartSetHeader(1, b"\x0a" * 32))


def _replaced(c):
    c.signatures = c.signatures[:3]


def _appended(c):
    c.signatures.append(CommitSig.new_absent())


def _slot_replaced(c):
    c.signatures[0] = CommitSig.new_absent()


@pytest.mark.parametrize("change", [_in_place, _timestamp, _flag, _round, _block_id, _replaced,
                                    _appended, _slot_replaced],
                         ids=["slot_in_place", "slot_timestamp", "slot_flag", "field_round",
                              "field_block_id", "signatures_replaced", "signatures_appended",
                              "slot_replaced"])
def test_a_change_gives_fresh_bytes(change):
    commit = _commit(5)
    before = _kept_encode(commit)
    change(commit)
    after = _kept_encode(commit)
    assert after != before
    assert after == (commit.to_proto().encode(),
                     hash_from_byte_slices([cs.to_proto().encode() for cs in commit.signatures]))


def test_a_slot_shared_by_two_commits_is_encoded_once_and_changed_for_both():
    a = _commit(3)
    b = Commit(height=a.height, round=a.round, block_id=a.block_id, signatures=list(a.signatures))
    a.encode()
    assert all(cs._enc is not None for cs in b.signatures)  # b's pass reuses a's leaves
    assert b.encode() == b.to_proto().encode()
    a.signatures[0].signature = b"\x02" * 64
    assert a.encode() == a.to_proto().encode() and b.encode() == b.to_proto().encode()


def _events(event: str) -> float:
    return sum(v for _, labels, v in hash_metrics().cache_events.samples()
               if labels == {"site": "commit_bytes", "event": event})


def test_each_read_is_counted_once_as_kept_or_encoded():
    commit = _commit(4)
    (e0, k0), miss0, hit0 = commit_reads(), _events("miss"), _events("hit")
    commit.hash()
    commit.encode()
    _block(commit).encode()  # fill_header finds last_commit_hash empty: hash() and encode()
    assert commit_reads() == (e0 + 1, k0 + 3)
    assert (_events("miss"), _events("hit")) == (miss0 + 1, hit0 + 3)
    commit.round = 2
    commit.encode()
    assert commit_reads() == (e0 + 2, k0 + 3)


def _old_save_block(db: MemDB, block: Block, parts: PartSet, seen: Commit) -> None:
    """What BlockStore.save_block wrote before commits kept their bytes:
    every record encoded anew from the proto path."""
    height = block.header.height
    meta = BlockMeta(block_id=BlockID(block.hash(), parts.header),
                     block_size=len(block.to_proto().encode()), header=block.header,
                     num_txs=len(block.txs))
    db.set(_h(KEY_META, height), meta.to_proto().encode())
    db.set(b"BH:" + block.hash(), height.to_bytes(8, "big"))
    for i in range(parts.total()):
        db.set(_h(b"P:", height) + b":" + i.to_bytes(4, "big"), parts.get_part(i).to_proto().encode())
    db.set(_h(KEY_COMMIT, height - 1), block.last_commit.to_proto().encode())
    db.set(_h(KEY_SEEN_COMMIT, height), seen.to_proto().encode())


def test_the_store_writes_what_it_wrote_for_a_chain_of_churn_blocks():
    """A chain whose set replaces a key a block, replayed as the
    blocksync reactor saves it (each block's parts made by the block,
    the seen commit the next block's last commit), against a store
    written the old way: the same records, byte for byte."""
    from benchmark import chain_churn

    config = {"validators": 6, "voting_power": 10, "txs_per_block": 2, "chain_id": "kept-churn",
              "blocks": 9, "rotation": {"validators_per_block": 1}}
    chain = chain_churn.build(config, 2147483659)
    blocks = [chain.block_store.load_block(h) for h in range(1, config["blocks"] + 1)]
    new_db, old_db = MemDB(), MemDB()
    store = BlockStore(new_db)
    e0, k0 = commit_reads()
    parts = blocks[0].make_part_set()
    for first, second in zip(blocks, blocks[1:]):
        old = Block.from_proto(first.to_proto())
        _old_save_block(old_db, old, PartSet.from_data(old.to_proto().encode(), BLOCK_PART_SIZE_BYTES),
                        Commit.from_proto(second.last_commit.to_proto()))
        ahead = second.make_part_set()  # the verify-ahead of the next height, before the save
        store.save_block(first, parts, second.last_commit)
        parts = ahead
    saved = len(blocks) - 1
    records = {k: v for k, v in new_db.iterator() if not k.startswith(b"blockStore")}
    assert records == dict(old_db.iterator())
    assert len(records) >= 5 * saved
    # each commit encoded once, when the block that carries it makes its
    # parts; the size, the last commit and the seen commit of every save
    # read bytes already made
    encoded, kept = commit_reads()
    assert (encoded - e0, kept - k0) == (saved + 1, 3 * saved)
