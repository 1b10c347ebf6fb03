"""A light block decodes what is read.

`pb.LightBlock.decode` + `LightBlock.from_proto` build the header; the
validator set (`pb.LightBlock.validator_set`) and the commit
(`pb.SignedHeader.commit`) stay their slice of the buffer until
something reads them (proto/message.py `lazy`, types/light_block.py
`_Deferred`). Held here: what a malformed part raises and where, that a
part once read is an ordinary object (the benchmark's refusal probes
mutate one and re-encode), that a block built either way compares,
prints and copies alike, and that two threads may read one part.
"""

from __future__ import annotations

import copy
import gc
import json
import os
import sys
import threading
import weakref

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import chain as chainlib  # noqa: E402
from tendermint_tpu.proto import messages as pb  # noqa: E402
from tendermint_tpu.proto import wire  # noqa: E402
from tendermint_tpu.proto.message import Field, Message, _Unread  # noqa: E402
from tendermint_tpu.types.light_block import LightBlock, SignedHeader, _Deferred  # noqa: E402

SEED = 2147491007  # past 31 bits, as the driver's seeds are
SIZES = [4, 150, 1000]
GARBAGE = b"\xff" * 24  # a tag whose varint never ends


@pytest.fixture(scope="module")
def blocks():
    """validators -> (chain, the light block at height 2 from ready objects, its encoding)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "chain-4.json"), encoding="utf-8") as fh:
        config = json.load(fh)
    out = {}
    for n in SIZES:
        chain = chainlib.build({**config, "validators": n, "blocks": 2, "chain_id": f"lazy-{n}"}, SEED)
        lb = LightBlock(SignedHeader(chain.block_store.load_block_meta(2).header,
                                     chain.block_store.load_seen_commit(2)), chain.validators)
        out[n] = (chain, lb, lb.to_proto().encode())
    return out


def decoded(raw: bytes) -> LightBlock:
    return LightBlock.from_proto(pb.LightBlock.decode(raw))


def eager(raw: bytes) -> LightBlock:
    """The same block from the constructors that take ready objects, as `from_proto` built it before."""
    from tendermint_tpu.types.block import Commit, Header
    from tendermint_tpu.types.validator_set import ValidatorSet

    p = pb.LightBlock.decode(raw)
    return LightBlock(SignedHeader(Header.from_proto(p.signed_header.header), Commit.from_proto(p.signed_header.commit)),
                      ValidatorSet.from_proto(p.validator_set))


def delimited(number: int, body: bytes) -> bytes:
    return wire.encode_tag(number, wire.WIRE_BYTES) + wire.encode_varint(len(body)) + body


def with_parts(lb: LightBlock, commit: bytes | None = None, validator_set: bytes | None = None) -> bytes:
    """The block's encoding with the inside of its parts replaced, every length around them right."""
    p = lb.to_proto()
    commit = p.signed_header.commit.encode() if commit is None else commit
    vals = p.validator_set.encode() if validator_set is None else validator_set
    signed_header = pb.SignedHeader.encode_field("header", p.signed_header.header) + delimited(2, commit)
    return delimited(1, signed_header) + delimited(2, vals)


def with_part(lb: LightBlock, part: str, body: bytes) -> bytes:
    return with_parts(lb, **{part: body})


def unread(lb: LightBlock) -> set[str]:
    """The parts of a block still held as what they arrived as."""
    held = {"commit": lb.signed_header.__dict__["_commit"], "validator_set": lb.__dict__["_validator_set"]}
    return {part for part, v in held.items() if isinstance(v, _Deferred)}


# ------------------------------------------------------------ what is decoded


@pytest.mark.parametrize("n", SIZES)
def test_a_decoded_block_has_read_neither_part_and_hashes_its_header(blocks, n):
    chain, ready, raw = blocks[n]
    p = pb.LightBlock.decode(raw)
    assert isinstance(p.__dict__["_validator_set"], _Unread)
    assert isinstance(p.signed_header.__dict__["_commit"], _Unread)
    lb = LightBlock.from_proto(p)
    assert unread(lb) == {"commit", "validator_set"}
    assert lb.signed_header.hash() == chain.block_hashes[1] == ready.signed_header.hash()
    assert lb.height == lb.signed_header.height == 2 and lb.signed_header.header == ready.signed_header.header
    assert unread(lb) == {"commit", "validator_set"}  # none of that read a part
    lb.validate_basic(chain.chain_id)
    assert unread(lb) == set()
    # read straight from the bytes: the message never decoded its parts
    assert isinstance(p.__dict__["_validator_set"], _Unread)
    assert isinstance(p.signed_header.__dict__["_commit"], _Unread)
    assert len(lb.validator_set.validators) == len(lb.signed_header.commit.signatures) == n
    # and the block holds objects, nothing of the message or the buffer it came from
    message = weakref.ref(p)
    del p
    gc.collect()
    assert message() is None


@pytest.mark.parametrize("part", ["validator_set", "commit"])
def test_a_part_malformed_inside_is_refused_by_whatever_reads_it(blocks, part):
    chain, ready, _ = blocks[4]
    raw = with_part(ready, part, GARBAGE)
    lb = decoded(raw)  # the framing is sound: decode and from_proto succeed
    assert lb.signed_header.hash() == chain.block_hashes[1]
    with pytest.raises(ValueError):
        lb.validate_basic(chain.chain_id)
    again = decoded(raw)
    with pytest.raises(ValueError, match="varint"):
        again.validator_set if part == "validator_set" else again.signed_header.commit
    with pytest.raises(ValueError):  # and again: a failed read leaves the part as it was
        again.validator_set if part == "validator_set" else again.signed_header.commit
    with pytest.raises(ValueError):
        getattr(pb.LightBlock.decode(raw) if part == "validator_set"
                else pb.LightBlock.decode(raw).signed_header, part)
    with pytest.raises(ValueError):
        again.to_proto()
    # the other part is sound and reads
    assert again.signed_header.commit.height == 2 if part == "validator_set" else again.validator_set.size() == 4


@pytest.mark.parametrize("part", ["validator_set", "commit"])
def test_outer_framing_is_still_checked_at_decode(blocks, part):
    _, ready, raw = blocks[4]
    sound = with_part(ready, part, GARBAGE)
    pb.LightBlock.decode(sound)
    with pytest.raises(ValueError, match="truncated length-delimited field"):
        pb.LightBlock.decode(sound[:-1])  # the part's length points past the buffer
    with pytest.raises(ValueError, match="truncated length-delimited field"):
        pb.LightBlock.decode(raw[: len(raw) // 2])
    with pytest.raises(ValueError, match="cannot skip wire type"):
        pb.LightBlock.decode(sound + b"\x7f")
    if part == "commit":
        p = ready.to_proto().signed_header
        inner = pb.SignedHeader.encode_field("header", p.header) + delimited(2, GARBAGE)
        pb.SignedHeader.decode(inner)
        with pytest.raises(ValueError, match="truncated length-delimited field"):
            pb.SignedHeader.decode(inner[:-3])


def test_an_occurrence_that_a_later_one_replaces_is_still_decoded(blocks):
    """Of two occurrences of a field the later is kept. Only that one
    stays unread: a malformed earlier one is refused at decode, as the
    eager codec refused it."""
    _, ready, raw = blocks[4]
    vals = ready.to_proto().validator_set.encode()
    assert pb.LightBlock.decode(raw + delimited(2, vals)).validator_set == ready.to_proto().validator_set
    with pytest.raises(ValueError, match="varint"):
        pb.LightBlock.decode(with_part(ready, "validator_set", GARBAGE) + delimited(2, vals))
    late = pb.LightBlock.decode(raw + delimited(2, GARBAGE))
    with pytest.raises(ValueError, match="varint"):
        late.validator_set


def test_a_part_the_message_does_not_carry_reads_as_none(blocks):
    chain, ready, _ = blocks[4]
    p = ready.to_proto()
    lb = decoded(delimited(1, pb.SignedHeader.encode_field("header", p.signed_header.header)))
    assert lb.signed_header.commit is None and lb.validator_set is None
    with pytest.raises(ValueError, match="missing commit"):
        lb.signed_header.validate_basic(chain.chain_id)
    lb.signed_header.commit = ready.signed_header.commit
    with pytest.raises(ValueError, match="missing validator set"):
        lb.validate_basic(chain.chain_id)


def test_only_a_nullable_sub_message_can_be_lazy():
    for bad in (Field(1, "bytes", "x", lazy=True),
                Field(1, "message", "x", msg_cls=pb.Timestamp, repeated=True, lazy=True),
                Field(1, "message", "x", msg_cls=pb.Timestamp, always_emit=True, lazy=True)):
        with pytest.raises(TypeError, match="lazy"):
            type("Bad", (Message,), {"fields": [bad]})


# ------------------------------------------------ a part read is an object


@pytest.mark.parametrize("n", SIZES)
def test_a_mutation_of_a_read_part_is_what_is_encoded(blocks, n):
    """The refusal probes' pattern (benchmark/drivers/light.py `_refusal`)."""
    _, ready, raw = blocks[n]
    forged = decoded(raw)
    cs = forged.signed_header.commit.signatures[n // 2]
    cs.signature = chainlib.flip_s(cs.signature)
    forged_raw = forged.to_proto().encode()
    assert forged_raw != raw and len(forged_raw) == len(raw)
    back = decoded(forged_raw)
    assert back.signed_header.commit.signatures[n // 2].signature == cs.signature
    assert back.signed_header.commit.signatures[n // 2 - 1] == ready.signed_header.commit.signatures[n // 2 - 1]
    assert back.validator_set.hash() == ready.validator_set.hash()
    # the proto message alike: a field read is encoded from the object it became
    p = pb.LightBlock.decode(raw)
    p.signed_header.commit.signatures[0].signature = b"\x01" * 64
    p.validator_set.total_voting_power = 7
    q = pb.LightBlock.decode(p.encode())
    assert q.signed_header.commit.signatures[0].signature == b"\x01" * 64 and q.validator_set.total_voting_power == 7
    p.validator_set = None
    assert pb.LightBlock.decode(p.encode()).validator_set is None


@pytest.mark.parametrize("n", SIZES)
def test_an_untouched_block_encodes_to_the_bytes_it_came_from(blocks, n):
    _, _, raw = blocks[n]
    p = pb.LightBlock.decode(raw)
    assert p.encode() == raw
    assert isinstance(p.__dict__["_validator_set"], _Unread)  # encoding read nothing
    assert pb.LightBlockResponseProto(light_block=p).encode() == delimited(1, raw)
    assert decoded(raw).to_proto().encode() == raw
    half = decoded(raw)
    assert half.validator_set.size() == n and unread(half) == {"commit"}
    assert half.to_proto().encode() == raw


# ------------------------------------------------ ==, repr, copy, constructors


@pytest.mark.parametrize("n", SIZES[:2])
def test_blocks_built_both_ways_compare_print_and_copy_alike(blocks, n):
    _, built, raw = blocks[n]
    lazy, ready = decoded(raw), eager(raw)
    assert unread(ready) == set() and ready.validator_set.hash() == built.validator_set.hash()
    assert lazy == ready and ready == decoded(raw)
    assert unread(lazy) == set()  # == reads
    assert repr(decoded(raw)) == repr(ready)
    assert decoded(raw).signed_header == ready.signed_header != SignedHeader(ready.signed_header.header, None)
    for clone in (copy.copy(decoded(raw)), copy.deepcopy(decoded(raw)), copy.deepcopy(ready)):
        assert clone == ready
    shallow = copy.copy(lazy)
    shallow.validator_set = None  # a copy's part is its own
    assert lazy.validator_set is not None
    p = pb.LightBlock.decode(raw)
    assert p.copy() == p == ready.to_proto() and repr(pb.LightBlock.decode(raw)) == repr(ready.to_proto())
    assert pb.LightBlock.decode(raw).which() == "signed_header"


def test_the_constructors_take_ready_objects_as_they_did(blocks):
    _, ready, _ = blocks[4]
    header, commit, vals = ready.signed_header.header, ready.signed_header.commit, ready.validator_set
    by_position = LightBlock(SignedHeader(header, commit), vals)
    by_name = LightBlock(signed_header=SignedHeader(header=header, commit=commit), validator_set=vals)
    assert by_position == by_name == ready and unread(by_name) == set()
    assert by_name.signed_header.commit is commit and by_name.validator_set is vals
    with pytest.raises(TypeError):
        SignedHeader(header)
    with pytest.raises(TypeError):
        LightBlock(signed_header=ready.signed_header)
    assert pb.SignedHeader().commit is None and pb.LightBlock(validator_set=None).validator_set is None
    with pytest.raises(TypeError, match="unknown fields"):
        pb.LightBlock(validators=None)


def test_attack_evidence_refuses_a_malformed_part_where_it_is_decoded(blocks):
    """Evidence is verified in full, and a proposed block can carry it
    past the pool's check (pending evidence is matched by the header's
    hash): its conflicting block's parts are read at `from_proto`."""
    from tendermint_tpu.types.evidence import LightClientAttackEvidence, evidence_from_proto

    chain, ready, raw = blocks[4]
    sound = LightClientAttackEvidence(conflicting_block=decoded(raw), common_height=1, total_voting_power=40)
    back = evidence_from_proto(pb.Evidence.decode(pb.Evidence(light_client_attack_evidence=sound.to_proto()).encode()))
    assert unread(back.conflicting_block) == set()
    back.validate_basic()
    for part in ("validator_set", "commit"):
        bad = delimited(1, with_part(ready, part, GARBAGE)) + b"\x10\x01"
        p = pb.Evidence.decode(delimited(2, bad))
        with pytest.raises(ValueError, match="varint"):
            evidence_from_proto(p)


# ------------------------------------------------------------ two threads


@pytest.mark.parametrize("part", ["validator_set", "commit"])
def test_two_threads_reading_one_part_get_equal_objects(blocks, part):
    raw = blocks[150][2]
    ready = eager(raw)
    want = ready.validator_set if part == "validator_set" else ready.signed_header.commit
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(6):
            lb, got, start = decoded(raw), [], threading.Barrier(4)

            def read():
                start.wait(timeout=10)
                got.append(lb.validator_set if part == "validator_set" else lb.signed_header.commit)

            threads = [threading.Thread(target=read) for _ in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in threads) and len(got) == 4
            assert all(g == want for g in got)
            assert unread(lb) == {"validator_set", "commit"} - {part}
            assert (lb.validator_set if part == "validator_set" else lb.signed_header.commit) == want
    finally:
        sys.setswitchinterval(was)
