"""A light block's parts are decoded once.

`ValidatorSet.from_bytes` and `Commit.from_bytes` go from the wire to
the domain objects in one pass (proto/message.py `decoder_to`). Held
here: that on any buffer they return what `from_proto(pb.X.decode(buf))`
returns or raise what it raises, that a validator's leaf kept from that
pass is the `SimpleValidator` encoding of what was decoded, that
`_Deferred` takes the path its slot allows and says which, and that the
generated `decode` of every message class is what it was.
"""

from __future__ import annotations

import inspect
import os
import sys

import pytest

pytest.importorskip("hypothesis")

from hypothesis import given, settings, strategies as st  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tendermint_tpu import trace  # noqa: E402
from tendermint_tpu.crypto.ed25519 import Ed25519PubKey  # noqa: E402
from tendermint_tpu.metrics import light_metrics  # noqa: E402
from tendermint_tpu.proto import messages as pb  # noqa: E402
from tendermint_tpu.proto import wire  # noqa: E402
from tendermint_tpu.proto.message import Field, Message, _codec_of, _Unread  # noqa: E402
from tendermint_tpu.types.block import Commit  # noqa: E402
from tendermint_tpu.types.light_block import LightBlock  # noqa: E402
from tendermint_tpu.types.validator_set import Validator, ValidatorSet  # noqa: E402

PARTS = {"validator_set": (ValidatorSet, pb.ValidatorSet), "commit": (Commit, pb.Commit)}
INT64 = st.integers(min_value=-(2**63), max_value=2**63 - 1)
POWERS = [0, 1, 127, 128, 2**62]


def delimited(number: int, body: bytes) -> bytes:
    return wire.encode_tag(number, wire.WIRE_BYTES) + wire.encode_varint(len(body)) + body


def verdict(fn):
    """What a decoder made of a buffer: its object, or its refusal."""
    try:
        return "built", fn()
    except Exception as e:  # noqa: BLE001 - whatever it is, both paths must raise it
        return type(e), str(e)


def same_verdict(part: str, buf: bytes):
    cls, message = PARTS[part]
    direct = verdict(lambda: cls.from_bytes(buf))
    two_pass = verdict(lambda: cls.from_proto(message.decode(buf)))
    assert direct == two_pass
    # in place, inside a larger buffer, as `_Deferred` calls it
    assert verdict(lambda: cls.from_bytes(b"\x7f" * 3 + buf + b"\xff" * 2, 3, 3 + len(buf))) == two_pass
    if direct[0] == "built" and part == "validator_set":
        assert [v.bytes() for v in direct[1].validators] == [v.bytes() for v in two_pass[1].validators]
        assert direct[1].hash() == two_pass[1].hash()
    return direct


# ------------------------------------------------------------ random messages

_keys = st.one_of(
    st.builds(lambda k: pb.PublicKey(ed25519=k), st.binary(min_size=32, max_size=32)),
    st.builds(lambda k: pb.PublicKey(ed25519=k), st.binary(max_size=40)),
    st.builds(lambda k: pb.PublicKey(secp256k1=k), st.binary(min_size=33, max_size=33)),
    st.builds(lambda k: pb.PublicKey(sr25519=k), st.binary(min_size=32, max_size=32)),
    st.just(pb.PublicKey()),
)
_validators = st.builds(
    lambda a, k, p, q: pb.Validator(address=a, pub_key=k, voting_power=p, proposer_priority=q),
    st.binary(max_size=24), _keys, st.one_of(st.sampled_from(POWERS), INT64), INT64)
_sound_validators = st.builds(
    lambda a, k, p, q: pb.Validator(address=a, pub_key=pb.PublicKey(ed25519=k), voting_power=p, proposer_priority=q),
    st.binary(min_size=20, max_size=20), st.binary(min_size=32, max_size=32),
    st.integers(min_value=0, max_value=2**56), INT64)  # seven of them stay under MAX_TOTAL_VOTING_POWER


def _sets(validators):
    return st.builds(lambda vals, proposer, total: pb.ValidatorSet(validators=vals, proposer=proposer, total_voting_power=total),
                     st.lists(validators, max_size=6), st.one_of(st.none(), validators), INT64)


_timestamps = st.one_of(st.just(pb.Timestamp()), st.builds(lambda s, n: pb.Timestamp(seconds=s, nanos=n), INT64,
                                                            st.integers(min_value=-(2**31), max_value=2**31 - 1)))
_sigs = st.builds(lambda f, a, t, s: pb.CommitSig(block_id_flag=f, validator_address=a, timestamp=t, signature=s),
                  st.integers(min_value=0, max_value=5), st.binary(max_size=24), _timestamps, st.binary(max_size=70))
_block_ids = st.builds(lambda h, t, ph: pb.BlockID(hash=h, part_set_header=pb.PartSetHeader(total=t, hash=ph)),
                       st.binary(max_size=34), st.integers(min_value=0, max_value=2**32 - 1), st.binary(max_size=34))
_commits = st.builds(lambda h, r, b, s: pb.Commit(height=h, round=r, block_id=b, signatures=s),
                     INT64, st.integers(min_value=-(2**31), max_value=2**31 - 1), _block_ids, st.lists(_sigs, max_size=6))
MESSAGES = {"validator_set": _sets(_validators), "commit": _commits}


@given(_sets(_sound_validators))
@settings(max_examples=150, deadline=None)
def test_a_sound_validator_set_is_built_equal_with_every_leaf_kept(p):
    state, vs = same_verdict("validator_set", p.encode())
    assert state == "built" and vs.size() == len(p.validators)
    for v in vs.validators + ([vs.proposer] if vs.proposer else []):
        kept = v._bytes_cache
        assert kept[0] is v.pub_key and kept[1] == v.voting_power
        assert kept[2] == pb.SimpleValidator(pub_key=pb.PublicKey(ed25519=v.pub_key.bytes()), voting_power=v.voting_power).encode()
    assert vs.to_proto().encode() == ValidatorSet.from_proto(p).to_proto().encode()


@pytest.mark.parametrize("part", sorted(PARTS))
@given(data=st.data())
@settings(max_examples=200, deadline=None)
def test_any_message_is_built_equal_or_refused_alike(part, data):
    same_verdict(part, data.draw(MESSAGES[part]).encode())


def _truncated(buf, data, part=None):
    return buf[: data.draw(st.integers(min_value=0, max_value=max(len(buf) - 1, 0)))]


def _byte_flipped(buf, data, part=None):
    if not buf:
        return buf
    at = data.draw(st.integers(min_value=0, max_value=len(buf) - 1))
    return buf[:at] + bytes((buf[at] ^ data.draw(st.integers(min_value=1, max_value=255)),)) + buf[at + 1:]


def _field_repeated(buf, data, part):
    """The message followed by another's fields: of a single field the
    later occurrence is kept, a repeated one grows."""
    return buf + data.draw(MESSAGES[part]).encode()


def _unknown_field_inserted(buf, data, part=None):
    number = data.draw(st.integers(min_value=5, max_value=3000))
    unknown = data.draw(st.sampled_from([
        wire.encode_tag(number, wire.WIRE_VARINT) + wire.encode_varint(data.draw(st.integers(min_value=0, max_value=2**64 - 1))),
        wire.encode_tag(number, wire.WIRE_FIXED64) + b"\x01" * 8,
        wire.encode_tag(number, wire.WIRE_FIXED32) + b"\x02" * 4,
        delimited(number, data.draw(st.binary(max_size=9))),
        wire.encode_tag(number, 3),  # a group: no decoder skips it
    ]))
    return data.draw(st.sampled_from([unknown + buf, buf + unknown]))


def _arbitrary(buf, data, part=None):
    return data.draw(st.binary(max_size=96))


MUTATIONS = {"truncated": _truncated, "byte_flipped": _byte_flipped, "field_repeated": _field_repeated,
             "unknown_field_inserted": _unknown_field_inserted, "arbitrary_bytes": _arbitrary}


@pytest.mark.parametrize("mutation", sorted(MUTATIONS))
@pytest.mark.parametrize("part", sorted(PARTS))
@given(data=st.data())
@settings(max_examples=250, deadline=None)
def test_mutated_bytes_are_built_equal_or_refused_alike(part, mutation, data):
    same_verdict(part, MUTATIONS[mutation](data.draw(MESSAGES[part]).encode(), data, part))


# -------------------------------------- spellings a peer may choose, by hand

KEY = bytes(range(1, 33))
ADDRESS = bytes(range(20))


def validator_bytes(pub_key: bytes | None, power: bytes = b"\x18\x0a") -> bytes:
    """A `pb.Validator` by hand: address, the `pub_key` sub-message as given, the power's field as given."""
    return delimited(1, ADDRESS) + (b"" if pub_key is None else delimited(2, pub_key)) + power


@pytest.mark.parametrize("name,pub_key,refusal", [
    ("ed25519", delimited(1, KEY), None),
    ("ed25519_length_as_padded_varint", b"\x0a\xa0\x00" + KEY, None),
    ("ed25519_after_an_unknown_field", delimited(9, b"x") + delimited(1, KEY), None),
    ("ed25519_twice", delimited(1, bytes(32)) + delimited(1, KEY), None),
    ("ed25519_31_bytes", delimited(1, KEY[:31]), "ed25519 pubkey must be 32 bytes, got 31"),
    ("ed25519_33_bytes", delimited(1, KEY + b"\x00"), "ed25519 pubkey must be 32 bytes, got 33"),
    ("ed25519_empty", delimited(1, b""), "ed25519 pubkey must be 32 bytes, got 0"),
    ("two_arms", delimited(1, KEY) + delimited(2, b"\x02" + KEY), None),
    ("two_arms_the_other_way", delimited(2, b"\x02" + KEY) + delimited(1, KEY), None),
    ("secp256k1", delimited(2, b"\x02" + KEY), None),
    ("secp256k1_32_bytes", delimited(2, KEY), "secp256k1 pubkey must be 33 bytes, got 32"),
    ("sr25519", delimited(3, KEY), None),
    ("no_arm", b"", "unsupported proto pubkey arm None"),
    ("no_pub_key_field", None, "unsupported proto pubkey arm None"),
    ("an_arm_of_the_wrong_wire_type", b"\x08\x01", "PublicKey: bad wire type"),
    ("34_bytes_that_are_no_key", b"\x0a\x21" + KEY, "truncated length-delimited field"),
])
def test_a_public_key_however_spelled_is_the_key_pubkey_from_proto_makes(name, pub_key, refusal):
    one = validator_bytes(pub_key)
    buf = delimited(1, one) + delimited(1, validator_bytes(delimited(1, KEY))) + delimited(2, one)
    state, vs = same_verdict("validator_set", buf)
    if refusal is not None:
        assert (state, vs) == (ValueError, refusal)
        return
    if state != "built":  # sr25519 where this build has no such keys: refused alike, which is all that is held
        assert name == "sr25519"
        return
    assert vs.size() == 2 and vs.proposer == vs.validators[0]
    # the leaf is kept where the sub-message is the 34 bytes of the ed25519 arm alone; however
    # the key was spelled, the leaf is the canonical encoding of the key that was decoded
    assert (ValidatorSet.from_bytes(buf).validators[0]._bytes_cache is not None) == (name == "ed25519")
    assert vs.validators[0].bytes() == Validator(ADDRESS, vs.validators[0].pub_key, 10).bytes()


def test_a_refused_key_is_refused_after_everything_the_decoder_refuses():
    """Two passes decode the whole set before they build a validator: a
    key of the wrong length in the first validator does not hide a
    truncated second one, and a refused proposer comes after a refused validator."""
    bad_key = validator_bytes(delimited(1, KEY[:31]))
    sound = validator_bytes(delimited(1, KEY))
    assert same_verdict("validator_set", delimited(1, bad_key) + delimited(1, sound)[:-1]) == (
        ValueError, "truncated length-delimited field")
    assert same_verdict("validator_set", delimited(1, bad_key) + b"\x18\xff") == (ValueError, "truncated varint")
    other = validator_bytes(delimited(2, KEY))
    assert same_verdict("validator_set", delimited(2, other) + delimited(1, bad_key)) == (
        ValueError, "ed25519 pubkey must be 32 bytes, got 31")
    assert same_verdict("validator_set", delimited(2, other) + delimited(1, sound)) == (
        ValueError, "secp256k1 pubkey must be 33 bytes, got 32")


@pytest.mark.parametrize("name,power,value", [
    ("canonical", b"\x18\x0a", 10),
    ("padded_to_two_bytes", b"\x18\x8a\x00", 10),
    ("padded_to_ten_bytes", b"\x18\x8a" + b"\x80" * 8 + b"\x00", 10),
    ("zero_spelled_out", b"\x18\x00", 0),
    ("left_out", b"", 0),
    ("twice", b"\x18\x07\x18\x0a", 10),
    ("negative", b"\x18" + b"\xff" * 9 + b"\x01", -1),
    ("tag_as_padded_varint", b"\x98\x00\x0a", 10),
])
def test_the_leaf_is_built_from_the_value_and_not_from_the_peers_spelling(name, power, value):
    buf = delimited(1, validator_bytes(delimited(1, KEY), power))
    state, vs = same_verdict("validator_set", buf)
    assert state == "built" and vs.validators[0].voting_power == value
    canonical = pb.SimpleValidator(pub_key=pb.PublicKey(ed25519=KEY), voting_power=value).encode()
    assert vs.validators[0]._bytes_cache[2] == vs.validators[0].bytes() == canonical
    assert vs.hash() == ValidatorSet(validators=[Validator(ADDRESS, Ed25519PubKey(KEY), value)]).hash()


@pytest.mark.parametrize("name,buf", [
    ("varint_of_eleven_bytes", b"\x08" + b"\x80" * 10 + b"\x01"),
    ("varint_past_64_bits", b"\x08" + b"\xff" * 9 + b"\x7f"),
    ("height_in_a_length_delimited_field", b"\x0a\x01\x05"),
    ("signature_row_cut_short", delimited(4, b"\x08\x02\x12")),
    ("timestamp_with_an_unknown_group", delimited(4, delimited(3, b"\x3b"))),
    ("block_id_twice", delimited(3, delimited(1, b"a" * 32)) + delimited(3, delimited(2, b"\x08\x01"))),
    ("no_block_id_no_timestamp", b"\x08\x05" + delimited(4, b"\x08\x01")),
    ("nanos_past_a_second", delimited(4, delimited(3, b"\x08\x01\x10" + wire.encode_varint(2 * 10**9)))),
    ("negative_nanos", delimited(4, delimited(3, b"\x10" + wire.encode_varint(-5)))),
    ("zero_timestamp_spelled_out", delimited(4, delimited(3, b"\x08\x00\x10\x00"))),
    ("packed_signatures", delimited(4, b"")),
])
def test_a_commit_however_spelled_is_the_commit_from_proto_makes(name, buf):
    same_verdict("commit", buf)


# ------------------------------------------------------------------ the leaf


@pytest.mark.parametrize("power", POWERS)
def test_the_seeded_leaf_is_the_simple_validator_encoding(power):
    p = pb.ValidatorSet(validators=[pb.Validator(address=ADDRESS, pub_key=pb.PublicKey(ed25519=KEY), voting_power=power)])
    v = ValidatorSet.from_bytes(p.encode()).validators[0]
    want = pb.SimpleValidator(pub_key=pb.PublicKey(ed25519=KEY), voting_power=power).encode()
    assert v._bytes_cache == (v.pub_key, power, want) and v._bytes_cache[0] is v.pub_key
    assert v.bytes() is v._bytes_cache[2]
    assert Validator.from_proto(p.validators[0])._bytes_cache is None  # two passes seed nothing
    assert Validator.from_proto(p.validators[0]).bytes() == want


def test_a_validator_changed_after_decoding_is_encoded_again():
    p = pb.ValidatorSet(validators=[pb.Validator(address=ADDRESS, pub_key=pb.PublicKey(ed25519=KEY), voting_power=128)])
    v = ValidatorSet.from_bytes(p.encode()).validators[0]
    kept = v.bytes()
    v.voting_power = 129
    assert v.bytes() == pb.SimpleValidator(pub_key=pb.PublicKey(ed25519=KEY), voting_power=129).encode() != kept
    v.voting_power = 128
    v.pub_key = Ed25519PubKey(bytes(32))
    assert v.bytes() == pb.SimpleValidator(pub_key=pb.PublicKey(ed25519=bytes(32)), voting_power=128).encode() != kept
    # an equal key that is another object: the guard is by identity, so this encodes again, to the same bytes
    v.pub_key = Ed25519PubKey(KEY)
    assert v.bytes() == kept and v.bytes() is not kept
    assert v.copy()._bytes_cache is v._bytes_cache


# ---------------------------------------------------------------- `_Deferred`


@pytest.fixture(scope="module")
def block():
    """(chain id, a sound light block of 7 validators built in two passes, its encoding)."""
    import json

    from benchmark import chain as chainlib
    from tendermint_tpu.types.light_block import SignedHeader

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "chain-4.json"), encoding="utf-8") as fh:
        config = json.load(fh)
    chain = chainlib.build({**config, "validators": 7, "blocks": 2, "chain_id": "direct-7"}, 2147491007)
    lb = LightBlock(SignedHeader(chain.block_store.load_block_meta(2).header,
                                 chain.block_store.load_seen_commit(2)), chain.validators)
    raw = lb.to_proto().encode()
    p = pb.LightBlock.decode(raw)  # the same block in two passes: `==` on a set compares its total's memo too
    two_pass = LightBlock(SignedHeader(lb.signed_header.header, Commit.from_proto(p.signed_header.commit)),
                          ValidatorSet.from_proto(p.validator_set))
    return chain.chain_id, two_pass, raw


def alike(a: LightBlock, b: LightBlock) -> bool:
    """`==`, but for the memo of a set's total power, which `==` on a
    `ValidatorSet` compares and `to_proto` fills."""
    return (a.signed_header == b.signed_header and a.validator_set.validators == b.validator_set.validators
            and a.validator_set.proposer == b.validator_set.proposer)


def _rows():
    return {(labels["part"], labels["path"]): value for _, labels, value in light_metrics().part_rows.samples()}


def _traced(fn):
    """(what fn returned or raised, the `light.decode_part` spans' args, the rows counter's growth)."""
    before, was = _rows(), trace.enabled()
    trace.set_enabled(True)
    trace.clear()
    try:
        out = verdict(fn)
        spans = [ev["args"] for ev in trace.export()["traceEvents"] if ev.get("name") == "light.decode_part"]
    finally:
        trace.set_enabled(was)
        trace.clear()
    grown = {k: v - before.get(k, 0.0) for k, v in _rows().items() if v != before.get(k, 0.0)}
    return out, spans, grown


def test_a_part_still_bytes_is_built_directly_and_the_span_and_counter_say_so(block):
    chain_id, ready, raw = block
    p = pb.LightBlock.decode(raw)
    lb = LightBlock.from_proto(p)
    out, spans, grown = _traced(lambda: lb.validate_basic(chain_id))
    assert out == ("built", None)
    assert sorted((a["part"], a["path"], a["rows"]) for a in spans) == [("commit", "direct", 7), ("validator_set", "direct", 7)]
    assert grown == {("commit", "direct"): 7, ("validator_set", "direct"): 7}
    assert isinstance(p.__dict__["_validator_set"], _Unread) and isinstance(p.signed_header.__dict__["_commit"], _Unread)
    assert alike(lb, ready) and lb.validator_set.hash() == ready.validator_set.hash()
    assert all(v._bytes_cache is not None for v in lb.validator_set.validators)


def test_a_part_that_is_a_message_is_built_from_it(block):
    chain_id, ready, raw = block
    made_here = LightBlock.from_proto(ready.to_proto())  # a block built in this process: `pb` objects all through
    p = pb.LightBlock.decode(raw)
    p.validator_set.total_voting_power, p.signed_header.commit.round  # noqa: B018 - somebody read the parts through the message
    read_through = LightBlock.from_proto(p)
    for lb in (made_here, read_through):
        out, spans, grown = _traced(lambda lb=lb: lb.validate_basic(chain_id))
        assert out == ("built", None)
        assert sorted((a["part"], a["path"], a["rows"]) for a in spans) == [("commit", "message", 7), ("validator_set", "message", 7)]
        assert grown == {("commit", "message"): 7, ("validator_set", "message"): 7}
        assert alike(lb, ready)


def test_either_path_builds_equal_blocks(block):
    _, ready, raw = block
    direct = LightBlock.from_proto(pb.LightBlock.decode(raw))
    message = LightBlock.from_proto(ready.to_proto())
    assert alike(direct, message) and alike(direct, ready) and repr(direct) == repr(message)
    assert direct.to_proto().encode() == message.to_proto().encode() == raw
    assert direct.validator_set.hash() == message.validator_set.hash()


@pytest.mark.parametrize("part", sorted(PARTS))
def test_a_malformed_part_raises_what_it_raised_and_stays_unread(block, part):
    _, ready, _ = block
    p = ready.to_proto()
    garbage = b"\xff" * 24
    commit = garbage if part == "commit" else p.signed_header.commit.encode()
    vals = garbage if part == "validator_set" else p.validator_set.encode()
    raw = delimited(1, pb.SignedHeader.encode_field("header", p.signed_header.header) + delimited(2, commit)) + delimited(2, vals)
    lb = LightBlock.from_proto(pb.LightBlock.decode(raw))
    holder, slot = (lb, "_validator_set") if part == "validator_set" else (lb.signed_header, "_commit")
    held = holder.__dict__[slot]

    def read():
        return lb.validator_set if part == "validator_set" else lb.signed_header.commit

    out, spans, grown = _traced(read)
    assert out == (ValueError, "varint too long") == verdict(lambda: PARTS[part][1].decode(garbage))
    assert holder.__dict__[slot] is held  # the attribute is as it was
    assert [(a["part"], "path" in a) for a in spans] == [(part, False)] and grown == {}
    assert _traced(read)[0] == out  # and is refused again
    other = lb.signed_header.commit if part == "validator_set" else lb.validator_set
    assert other.size() == 7


def test_a_part_the_message_does_not_carry_is_none_by_the_message_path(block):
    _, ready, _ = block
    header = pb.SignedHeader.encode_field("header", ready.to_proto().signed_header.header)
    lb = LightBlock.from_proto(pb.LightBlock.decode(delimited(1, header)))
    out, spans, grown = _traced(lambda: (lb.validator_set, lb.signed_header.commit))
    assert out == ("built", (None, None))
    assert sorted((a["part"], a["path"], a["rows"]) for a in spans) == [("commit", "message", 0), ("validator_set", "message", 0)]
    assert grown == {}
    empty = LightBlock.from_proto(pb.LightBlock.decode(delimited(1, header + delimited(2, b"")) + delimited(2, b"")))
    assert empty.validator_set == ValidatorSet() and empty.signed_header.commit == Commit()


# ---------------------------------------------------- the generator's targets


def _message_classes():
    return [c for _, c in inspect.getmembers(pb, inspect.isclass) if issubclass(c, Message) and c is not Message]


def test_asking_for_a_builder_decoder_changes_no_generated_source():
    classes = _message_classes()
    before = {c: (_codec_of(c)._decode_source(), _codec_of(c)._encode_source(), _codec_of(c)._init_source()) for c in classes}
    functions = {c: (_codec_of(c).decode, _codec_of(c).encode, _codec_of(c).init) for c in classes}
    ValidatorSet.from_bytes(b"")
    Commit.from_bytes(b"")
    for c in classes:
        c.decoder_to(lambda *values: values)
    for c in classes:
        codec = _codec_of(c)
        assert (codec._decode_source(), codec._encode_source(), codec._init_source()) == before[c]
        assert (codec.decode, codec.encode, codec.init) == functions[c]
    # and the two targets differ in their last lines alone
    for c in (pb.Validator, pb.CommitSig, pb.Commit, pb.ValidatorSet, pb.Timestamp, pb.BlockID):
        eager = _codec_of(c)._decode_source().split("\n")
        direct = _codec_of(c)._decode_source(()).split("\n")
        loop = eager.index("    msg = new(cls)")
        assert direct[:loop] == eager[:loop] and len(direct) == loop + 1 and direct[loop].startswith("    return build(")


def _values(cls, msg):
    return tuple(msg.__dict__["_" + f.name] if f.lazy else getattr(msg, f.name) for f in cls.fields)


def _plain(v):
    """An unread part as what it holds, so that two of them compare."""
    return ("unread", v.cls, v.buf[v.start:v.end]) if isinstance(v, _Unread) else v


@given(st.binary(max_size=160))
@settings(max_examples=150, deadline=None)
def test_every_class_decodes_to_a_builder_what_it_decodes_to_a_message(buf):
    """The second target, with no decoder given for any field, against
    `decode` on arbitrary bytes, for every message class of the schema."""
    for cls in _message_classes():
        if cls is pb.PublicKey:
            continue  # its `decode` is its own, not the generator's
        to_tuple = cls.decoder_to(lambda *values: values)
        direct = verdict(lambda: tuple(_plain(v) for v in to_tuple(buf, 0, len(buf))))
        eager = verdict(lambda: tuple(_plain(v) for v in _values(cls, cls.decode(buf))))
        assert direct == eager, cls.__name__


class _Inner(Message):
    fields = [Field(1, "sfixed64", "a"), Field(2, "string", "s"), Field(3, "sint32", "z", repeated=True)]


class _Outer(Message):
    fields = [
        Field(1, "message", "one", always_emit=True, msg_cls=_Inner),
        Field(2, "message", "many", repeated=True, msg_cls=_Inner),
        Field(3, "fixed32", "f", repeated=True),
        Field(4, "message", "maybe", msg_cls=_Inner),
        Field(5, "message", "later", msg_cls=_Inner, lazy=True),
        Field(6, "bool", "b"),
    ]


_inner_to = _Inner.decoder_to(lambda a, s, z: ("inner", a, s, z))
_outer_to = _Outer.decoder_to(lambda *values: values, one=_inner_to, many=_inner_to, maybe=_inner_to)


def _outer_by_message(buf):
    def inner(m):
        return None if m is None else ("inner", m.a, m.s, m.z)

    m = _Outer.decode(buf)
    return inner(m.one), [inner(x) for x in m.many], m.f, inner(m.maybe), _plain(m.__dict__["_later"]), m.b


_inners = st.builds(lambda a, s, z: _Inner(a=a, s=s, z=z), INT64, st.text(max_size=5),
                    st.lists(st.integers(min_value=-(2**31), max_value=2**31 - 1), max_size=3))
_outers = st.builds(lambda one, many, f, maybe, later, b: _Outer(one=one, many=many, f=f, maybe=maybe, later=later, b=b),
                    _inners, st.lists(_inners, max_size=3), st.lists(st.integers(min_value=-(2**31), max_value=2**31 - 1), max_size=3),
                    st.one_of(st.none(), _inners), st.one_of(st.none(), _inners), st.booleans())


@given(data=st.data())
@settings(max_examples=300, deadline=None)
def test_fixed_width_packed_and_utf8_fields_are_read_and_refused_alike(data):
    """`ValueError` on a short fixed-width field, `UnicodeDecodeError`
    on a string, packed and unpacked repeats: a schema that has them all."""
    buf = data.draw(_outers).encode()
    mutate = MUTATIONS[data.draw(st.sampled_from(["truncated", "byte_flipped", "unknown_field_inserted", "arbitrary_bytes"]))]
    for b in (buf, mutate(buf, data)):
        direct = verdict(lambda b=b: tuple(_plain(v) for v in _outer_to(b, 0, len(b))))
        by_message = verdict(lambda b=b: _outer_by_message(b))
        if direct[0] == "built" and direct[1][0] is None:  # `one` absent: None to the builder, an empty message to `decode`
            direct = ("built", (("inner", 0, "", []),) + direct[1][1:])
        assert direct == by_message


@pytest.mark.parametrize("name,buf", [
    ("sfixed64_cut_short_in_a_single_sub_message", delimited(1, b"\x09\x01\x02")),
    ("sfixed64_with_nothing_after_its_tag_in_a_repeated_sub_message", delimited(2, b"\x09")),
    ("fixed32_cut_short_unpacked", b"\x1d\x01"),
    ("fixed32_cut_short_packed", delimited(3, b"\x01\x02\x03")),
])
def test_a_short_fixed_width_field_raises_what_struct_raises_today(name, buf):
    """A fixed-width field cut short is refused as a truncated field, a
    `ValueError`, by both paths: no longer `struct.error`, which a caller
    that guards a decode with `except ValueError` let through."""
    direct = verdict(lambda: _outer_to(buf, 0, len(buf)))
    assert direct == verdict(lambda: _outer_by_message(buf)) and direct[0] is ValueError
    assert direct[1].startswith("truncated fixed")


@pytest.mark.parametrize("name,subs,refusal", [
    ("a_field_the_class_has_not", {"nothing": _inner_to}, "only a sub-message"),
    ("a_scalar", {"b": _inner_to}, "only a sub-message"),
    ("a_lazy_sub_message", {"later": _inner_to}, "not lazy"),
])
def test_only_a_sub_message_that_is_not_lazy_takes_a_decoder(name, subs, refusal):
    with pytest.raises(TypeError, match=refusal):
        _Outer.decoder_to(lambda *values: values, **subs)
