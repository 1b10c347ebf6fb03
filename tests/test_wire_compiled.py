"""The compiled codec against the interpretive one it replaced.

`proto/message.py` compiles each class's `fields` into one decode and
one encode function. The loop it replaced is kept HERE, and only here,
as the plain reference: it reads `fields`, `ftype` strings and
a default built per field per message, on wire primitives of its own. Every
`Message` subclass of the package is held to it: same bytes out, same
objects in, the same verdict on hostile buffers. Then the three big
messages of the benchmark's 1000-validator chain, a structural guard
(calls counted under `sys.setprofile`, so it cannot flake) and the
sign-bytes template over a 1000-signature commit.
"""

from __future__ import annotations

import importlib
import json
import os
import pkgutil
import random
import struct
import sys

import pytest

import tendermint_tpu
from tendermint_tpu.proto import messages as pb
from tendermint_tpu.proto.message import Message

# -- the reference: the codec as it was before it was compiled ---------------

_U64 = (1 << 64) - 1
_VARINT = {"int32", "int64", "uint32", "uint64", "bool", "enum"}
_ZIGZAG = {"sint32", "sint64"}
_FIXED64 = {"sfixed64", "fixed64"}
_FIXED32 = {"sfixed32", "fixed32"}
_PACKABLE = _VARINT | _ZIGZAG | _FIXED64 | _FIXED32
_ZERO = {**dict.fromkeys(_PACKABLE, 0), "bool": False, "bytes": b"", "string": ""}


def ref_encode_varint(value: int) -> bytes:
    if value < 0:
        value &= _U64
    out = bytearray()
    while True:
        b = value & 0x7F
        value >>= 7
        if value:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def ref_decode_varint(buf: bytes, pos: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        if pos >= len(buf):
            raise ValueError("truncated varint")
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not (b & 0x80):
            if result > _U64:
                raise ValueError("varint overflows 64 bits")
            return result, pos
        shift += 7
        if shift >= 70:
            raise ValueError("varint too long")


def ref_decode_bytes(buf: bytes, pos: int) -> tuple[bytes, int]:
    n, pos = ref_decode_varint(buf, pos)
    if pos + n > len(buf):
        raise ValueError("truncated length-delimited field")
    return bytes(buf[pos : pos + n]), pos + n


def _ref_tag(number: int, wire_type: int) -> bytes:
    return ref_encode_varint((number << 3) | wire_type)


def _ref_wire_type(ftype: str) -> int:
    if ftype in _VARINT or ftype in _ZIGZAG:
        return 0
    if ftype in _FIXED64:
        return 1
    if ftype in _FIXED32:
        return 5
    return 2


def _ref_encode_scalar(ftype: str, value) -> bytes:
    if ftype in _VARINT:
        return ref_encode_varint(int(value))
    if ftype in _ZIGZAG:
        value = int(value)
        return ref_encode_varint((value << 1) ^ (value >> 63))
    if ftype in _FIXED64:
        return struct.pack("<q", int(value))
    if ftype in _FIXED32:
        return struct.pack("<i", int(value))
    if ftype == "bytes":
        value = bytes(value)
        return ref_encode_varint(len(value)) + value
    if ftype == "string":
        value = value.encode("utf-8")
        return ref_encode_varint(len(value)) + value
    raise TypeError(f"unknown scalar type {ftype}")


def _ref_decode_scalar(ftype: str, buf: bytes, pos: int):
    if ftype in _VARINT:
        raw, pos = ref_decode_varint(buf, pos)
        if ftype in ("int32", "int64") and raw >= 1 << 63:
            raw -= 1 << 64
        elif ftype == "bool":
            raw = bool(raw)
        return raw, pos
    if ftype in _ZIGZAG:
        raw, pos = ref_decode_varint(buf, pos)
        return (raw >> 1) ^ -(raw & 1), pos
    if ftype in _FIXED64:
        if pos + 8 > len(buf):
            raise ValueError("truncated fixed64 field")
        return struct.unpack_from("<q", buf, pos)[0], pos + 8
    if ftype in _FIXED32:
        if pos + 4 > len(buf):
            raise ValueError("truncated fixed32 field")
        return struct.unpack_from("<i", buf, pos)[0], pos + 4
    if ftype == "bytes":
        return ref_decode_bytes(buf, pos)
    if ftype == "string":
        b, pos = ref_decode_bytes(buf, pos)
        return b.decode("utf-8"), pos
    raise TypeError(f"unknown scalar type {ftype}")


def _ref_public_key_encode(msg) -> bytes:
    for num, name in ((1, "ed25519"), (2, "secp256k1"), (3, "sr25519")):
        v = getattr(msg, name)
        if v is not None:
            v = bytes(v)
            return _ref_tag(num, 2) + ref_encode_varint(len(v)) + v
    return b""


def _ref_public_key_decode(buf: bytes):
    msg = pb.PublicKey()
    pos = 0
    while pos < len(buf):
        raw, pos = ref_decode_varint(buf, pos)
        if raw & 7 != 2:
            raise ValueError("PublicKey: bad wire type")
        val, pos = ref_decode_bytes(buf, pos)
        name = {1: "ed25519", 2: "secp256k1", 3: "sr25519"}.get(raw >> 3)
        if name:
            setattr(msg, name, val)
    return msg


def ref_encode(msg) -> bytes:
    if type(msg) is pb.PublicKey:
        return _ref_public_key_encode(msg)
    out = bytearray()
    for f in sorted(type(msg).fields, key=lambda f: f.number):
        out += _ref_encode_field(f, getattr(msg, f.name))
    return bytes(out)


def _ref_encode_field(f, value) -> bytes:
    if f.repeated:
        if not value:
            return b""
        if f.ftype in _PACKABLE:
            payload = b"".join(_ref_encode_scalar(f.ftype, v) for v in value)
            return _ref_tag(f.number, 2) + ref_encode_varint(len(payload)) + payload
        out = bytearray()
        for v in value:
            if f.ftype == "message":
                body = ref_encode(v)
                out += _ref_tag(f.number, 2) + ref_encode_varint(len(body)) + body
            else:
                out += _ref_tag(f.number, _ref_wire_type(f.ftype)) + _ref_encode_scalar(f.ftype, v)
        return bytes(out)
    if f.ftype == "message":
        if value is None:
            return b""
        body = ref_encode(value)
        return _ref_tag(f.number, 2) + ref_encode_varint(len(body)) + body
    if value == _ZERO[f.ftype]:
        return b""
    return _ref_tag(f.number, _ref_wire_type(f.ftype)) + _ref_encode_scalar(f.ftype, value)


def ref_decode(cls, buf: bytes):
    if cls is pb.PublicKey:
        return _ref_public_key_decode(buf)
    msg = cls()
    by_number = {f.number: f for f in cls.fields}
    pos = 0
    while pos < len(buf):
        raw, pos = ref_decode_varint(buf, pos)
        f = by_number.get(raw >> 3)
        if f is None:
            pos = _ref_skip(buf, pos, raw & 7)
        else:
            pos = _ref_decode_field(msg, f, raw & 7, buf, pos)
    return msg


def _ref_decode_field(msg, f, wt: int, buf: bytes, pos: int) -> int:
    if f.ftype == "message":
        body, pos = ref_decode_bytes(buf, pos)
        sub = ref_decode(f.message_class(), body)
        if f.repeated:
            getattr(msg, f.name).append(sub)
        else:
            setattr(msg, f.name, sub)
        return pos
    if f.repeated and f.ftype in _PACKABLE and wt == 2:
        body, pos = ref_decode_bytes(buf, pos)
        sub = 0
        while sub < len(body):
            v, sub = _ref_decode_scalar(f.ftype, body, sub)
            getattr(msg, f.name).append(v)
        return pos
    v, pos = _ref_decode_scalar(f.ftype, buf, pos)
    if f.repeated:
        getattr(msg, f.name).append(v)
    else:
        setattr(msg, f.name, v)
    return pos


def _ref_skip(buf: bytes, pos: int, wt: int) -> int:
    if wt == 0:
        return ref_decode_varint(buf, pos)[1]
    if wt == 1:
        return pos + 8
    if wt == 5:
        return pos + 4
    if wt == 2:
        return ref_decode_bytes(buf, pos)[1]
    raise ValueError(f"cannot skip wire type {wt}")


# -- every Message subclass of the package -----------------------------------

# Found by reading, not by importing: every worker of a parallel run must
# collect the same cases, whatever it has imported so far.
_PACKAGE_DIR = os.path.dirname(tendermint_tpu.__file__)


def _modules_naming_message() -> list[str]:
    names = []
    for dirpath, _, files in os.walk(_PACKAGE_DIR):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            with open(path, encoding="utf-8") as fh:
                if "(Message)" not in fh.read():
                    continue
            rel = os.path.relpath(path, os.path.dirname(_PACKAGE_DIR))[: -len(".py")]
            names.append(rel.replace(os.sep, "."))
    return sorted(names)


def _all_subclasses(cls):
    for sub in cls.__subclasses__():
        yield sub
        yield from _all_subclasses(sub)


for _name in _modules_naming_message():
    importlib.import_module(_name)
CLASSES = sorted(
    {c for c in _all_subclasses(Message) if c.__module__.startswith("tendermint_tpu.")},
    key=lambda c: (c.__module__, c.__qualname__),
)
_by_class = pytest.mark.parametrize("cls", CLASSES, ids=lambda c: f"{c.__module__.split('.', 1)[1]}.{c.__qualname__}")


def test_no_message_class_is_left_out():
    """Importing every module of the package finds no class beyond CLASSES."""
    for info in pkgutil.walk_packages(tendermint_tpu.__path__, "tendermint_tpu."):
        leaf = info.name.rsplit(".", 1)[1]
        if leaf == "__main__" or not leaf.isidentifier():  # the CLI's entry; native/prep-<key>.so
            continue
        importlib.import_module(info.name)
    found = {c for c in _all_subclasses(Message) if c.__module__.startswith("tendermint_tpu.")}
    assert found == set(CLASSES)
    assert len(CLASSES) >= 136


# -- seeded instances ----------------------------------------------------------

_INTS = {
    "int32": [0, 1, -1, 127, 128, 300, 2**31 - 1, -(2**31)],
    "int64": [0, 1, -1, 127, 128, 16384, 2**63 - 1, -(2**63), 1_700_000_000],
    "uint32": [0, 1, 127, 128, 16383, 16384, 2**32 - 1],
    "uint64": [0, 1, 127, 128, 2**32, 2**64 - 1],
    "enum": [0, 1, 2, 3, 32],
    "sint32": [0, 1, -1, 63, -64, 64, 2**31 - 1, -(2**31)],
    "sint64": [0, 1, -1, 2**63 - 1, -(2**63)],
    "sfixed64": [0, 1, -1, 2**63 - 1, -(2**63)],
    "fixed64": [0, 1, 2**63 - 1],
    "sfixed32": [0, 1, -1, 2**31 - 1, -(2**31)],
    "fixed32": [0, 1, 2**31 - 1],
}


def _scalar(rng: random.Random, ftype: str):
    if ftype in _INTS:
        return rng.choice(_INTS[ftype])
    if ftype == "bool":
        return rng.random() < 0.5
    if ftype == "bytes":
        return rng.randbytes(rng.choice([0, 1, 20, 32, 64, 127, 128, 300]))
    if ftype == "string":
        return rng.choice(["", "a", "chain-1k", "käse ☃", "x" * 200])
    raise AssertionError(ftype)


def make_instance(rng: random.Random, cls, depth: int = 0):
    """A random message of `cls`: zero values, negative numbers, empty and
    absent sub-messages, empty and long repeated fields all occur."""
    if cls is pb.PublicKey:
        arm = rng.choice([None, "ed25519", "secp256k1", "sr25519"])
        return cls(**({arm: rng.randbytes(rng.choice([0, 32, 33]))} if arm else {}))
    kwargs = {}
    for f in cls.fields:
        if rng.random() < 0.25:
            continue  # left at its default
        count = rng.choice([0, 1, 2, 5]) if f.repeated else 1
        if f.ftype == "message":
            sub = f.message_class()
            if depth >= 3:
                values = [sub() for _ in range(count)]
            else:
                values = [sub() if rng.random() < 0.2 else make_instance(rng, sub, depth + 1) for _ in range(count)]
        else:
            values = [_scalar(rng, f.ftype) for _ in range(count)]
        kwargs[f.name] = values if f.repeated else values[0]
    return cls(**kwargs)


def same(a, b) -> bool:
    """Equal, and of the same types all the way down (1 is not True here)."""
    if type(a) is not type(b):
        return False
    if isinstance(a, Message):
        return all(same(getattr(a, f.name), getattr(b, f.name)) for f in type(a).fields)
    if isinstance(a, list):
        return len(a) == len(b) and all(same(x, y) for x, y in zip(a, b))
    return a == b


def outcome(decode, buf: bytes):
    try:
        return decode(buf)
    except Exception as e:  # noqa: BLE001 - the verdict itself is what is compared
        return type(e), str(e)


def decode_and_read(cls, buf: bytes):
    """`decode`, then every sub-message read: a `lazy` field is decoded
    by its first read, and the verdict on a buffer is that of both."""

    def read(msg):
        for f in type(msg).fields:
            if f.ftype == "message":
                value = getattr(msg, f.name)
                for sub in value if f.repeated else [value]:
                    if sub is not None:
                        read(sub)
        return msg

    return read(cls.decode(buf))


def assert_same_verdict(cls, buf: bytes):
    got = outcome(lambda b: decode_and_read(cls, b), buf)
    want = outcome(lambda b: ref_decode(cls, b), buf)
    if isinstance(want, Message):
        assert same(got, want), (buf.hex(), got, want)
    elif _reads_lazily(cls) and not isinstance(got, Message):
        # two faults in one buffer: the outer framing is checked before the inside of a
        # lazy part, so the fault named may be the other one; the exception's type holds
        assert got[0] is want[0], (buf.hex(), got, want)
    else:
        assert got == want, (buf.hex(), got, want)


def _reads_lazily(cls, seen=None) -> bool:
    seen = seen if seen is not None else set()
    if cls in seen:
        return False
    seen.add(cls)
    return any(f.ftype == "message" and (f.lazy or _reads_lazily(f.message_class(), seen)) for f in cls.fields)


def _instances(cls, n: int = 12):
    rng = random.Random(f"{cls.__module__}.{cls.__qualname__}")
    return rng, [cls()] + [make_instance(rng, cls) for _ in range(n)]


@_by_class
def test_encodes_the_reference_bytes_and_decodes_its_objects(cls):
    _, instances = _instances(cls)
    for msg in instances:
        raw = msg.encode()
        assert raw == ref_encode(msg), msg
        assert_same_verdict(cls, raw)
        back = cls.decode(raw)
        assert back.encode() == raw
        assert same(msg.copy(), back)
        if cls is not pb.PublicKey:  # a field alone, as the sign-bytes template takes it
            for f in cls.fields:
                assert cls.encode_field(f.name, getattr(msg, f.name)) == _ref_encode_field(f, getattr(msg, f.name))


_VARINT_11_BYTES = b"\xff" * 10 + b"\x01"
_VARINT_OVER_64_BITS = b"\xff" * 9 + b"\x7f"


def hostile_buffers(rng: random.Random, cls, raws: list[bytes]):
    numbers = [f.number for f in cls.fields]
    unknown = max(numbers, default=0) + 1
    for raw in raws:
        cuts = {0, 1, len(raw) - 1, len(raw) // 2} | {rng.randrange(len(raw) + 1) for _ in range(6)}
        for cut in sorted(c for c in cuts if 0 <= c < len(raw)):
            yield raw[:cut]
        yield raw + raw  # every singular field twice: the later one wins
        if raw:
            flipped = bytearray(raw)
            for _ in range(3):
                flipped[rng.randrange(len(raw))] ^= 1 << rng.randrange(8)
            yield bytes(flipped)
    raw = raws[-1]
    for number in numbers + [unknown, 1 << 20, (1 << 61) - 1]:
        for wt in range(8):
            tag = ref_encode_varint((number << 3) | wt)
            for payload in (b"", b"\x00", b"\x05hello", b"\x05hel", b"\x80", b"\xac\x02", b"\x01\x02\x03\x04",
                            b"\x08" + bytes(8), b"\x09" + bytes(9), b"\x02\xff\xfe", bytes(12),
                            _VARINT_11_BYTES, _VARINT_OVER_64_BITS, b"\x0b" + _VARINT_11_BYTES):
                yield tag + payload
                yield raw + tag + payload + raw
    yield _VARINT_11_BYTES
    yield _VARINT_OVER_64_BITS + b"\x00"
    yield raw + b"\xff"


@_by_class
def test_refuses_and_accepts_what_the_reference_does(cls):
    rng, instances = _instances(cls, n=4)
    for buf in hostile_buffers(rng, cls, [m.encode() for m in instances]):
        assert_same_verdict(cls, buf)


def test_the_named_refusals_are_reached():
    """The verdicts above are the documented ones, not two codecs agreeing on something else."""
    for buf, message in (
        (b"\x08", "truncated varint"),
        (b"\x08" + _VARINT_11_BYTES, "varint too long"),
        (b"\x08" + _VARINT_OVER_64_BITS, "varint overflows 64 bits"),
        (b"\x12\x05abc", "truncated length-delimited field"),
        (b"\x7b", "cannot skip wire type 3"),
        (b"\x7c", "cannot skip wire type 4"),
        (b"\x7e", "cannot skip wire type 6"),
        (b"\x7f", "cannot skip wire type 7"),
    ):
        with pytest.raises(ValueError, match=message):
            pb.Header.decode(buf)
    h = pb.Header.decode(b"\x78\x01\x79" + bytes(8) + b"\x7a\x01x\x7d" + bytes(4) + b"\x18\x05\x18\x07")
    assert h.height == 7  # unknown fields of each wire type skipped; the later height wins
    assert pb.Header.decode(b"\x18" + b"\xff" * 9 + b"\x01").height == -1
    assert pb.Timestamp.decode(b"\x10" + b"\xff" * 9 + b"\x01").nanos == -1


def test_packed_and_unpacked_repeated_scalars_decode_alike():
    cls = next(c for c in CLASSES for f in c.fields if f.repeated and f.ftype in _PACKABLE)
    f = next(f for f in cls.fields if f.repeated and f.ftype in _PACKABLE)
    values = [0, 1, 300, 2**32 - 1]
    tag = (f.number << 3) | _ref_wire_type(f.ftype)
    unpacked = b"".join(ref_encode_varint(tag) + _ref_encode_scalar(f.ftype, v) for v in values)
    packed = cls(**{f.name: values}).encode()
    assert packed[0] == (f.number << 3) | 2
    for buf in (packed, unpacked, packed + unpacked):
        assert_same_verdict(cls, buf)
    assert getattr(cls.decode(unpacked), f.name) == values
    assert getattr(cls.decode(packed + unpacked), f.name) == values + values


def test_a_subclass_compiles_its_own_plan():
    class Child(pb.Timestamp):
        pass

    assert type(Child.decode(pb.Timestamp(seconds=5).encode())) is Child
    assert type(pb.Timestamp.decode(b"\x08\x05")) is pb.Timestamp
    assert Child(seconds=5, nanos=1).encode() == pb.Timestamp(seconds=5, nanos=1).encode()


def test_decode_takes_any_buffer_and_gives_bytes():
    raw = pb.BlockID(hash=b"h" * 32, part_set_header=pb.PartSetHeader(total=1, hash=b"p" * 32)).encode()
    for buf in (raw, bytearray(raw), memoryview(raw)):
        got = pb.BlockID.decode(buf)
        assert type(got.hash) is bytes and type(got.part_set_header.hash) is bytes
        assert got.encode() == raw


# -- the benchmark's chain at 1000 validators ----------------------------------


@pytest.fixture(scope="module")
def chain_1k():
    from benchmark import chain as chainlib

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "chain-1k.json"), encoding="utf-8") as fh:
        config = json.load(fh)
    config["blocks"] = 2
    return chainlib.build(config, 2147491007)


@pytest.fixture(scope="module")
def big_messages(chain_1k):
    from tendermint_tpu.types.light_block import LightBlock, SignedHeader

    store = chain_1k.block_store
    commit = store.load_seen_commit(2)
    vals = chain_1k.state_store.load_validators(2)
    light = LightBlock(SignedHeader(store.load_block_meta(2).header, commit), vals)
    return {
        "LightBlock": (pb.LightBlock, light.to_proto()),
        "Block": (pb.Block, store.load_block(2).to_proto()),
        "ValidatorSet": (pb.ValidatorSet, vals.to_proto()),
    }


@pytest.mark.parametrize("name", ["LightBlock", "Block", "ValidatorSet"])
def test_big_messages_round_trip_byte_for_byte(big_messages, name):
    cls, msg = big_messages[name]
    raw = msg.encode()
    assert len(raw) > 60_000
    assert raw == ref_encode(msg)
    back = cls.decode(raw)
    assert same(back, ref_decode(cls, raw))
    assert back.encode() == raw
    for cut in (len(raw) - 1, len(raw) // 2, 70_000 % len(raw)):
        assert_same_verdict(cls, raw[:cut])


def _python_calls(fn) -> dict[str, int]:
    """Python-level calls made under fn(), by qualified name."""
    calls: dict[str, int] = {}

    def profiler(frame, event, arg):
        if event == "call":
            code = frame.f_code
            name = getattr(code, "co_qualname", code.co_name)
            calls[name] = calls.get(name, 0) + 1
        elif event == "c_call":
            name = "builtin:" + arg.__name__
            calls[name] = calls.get(name, 0) + 1

    sys.setprofile(profiler)
    try:
        fn()
    finally:
        sys.setprofile(None)
    return calls


def _count_sub_messages(msg) -> int:
    n = 1
    for f in type(msg).fields:
        if f.ftype == "message":
            value = getattr(msg, f.name)
            for sub in value if f.repeated else [value]:
                if sub is not None:
                    n += _count_sub_messages(sub)
    return n


# Python-level calls a sub-message when decoding a 1000-validator light
# block: 22.3 with the interpretive codec, 2.5 with the compiled one (one a
# message, one a multi-byte varint, four more in PublicKey's own decode).
MAX_DECODE_CALLS_PER_SUB_MESSAGE = 3


def test_decoding_a_light_block_interprets_nothing(big_messages):
    cls, msg = big_messages["LightBlock"]
    raw = msg.encode()
    cls.decode(raw)  # compiles the plans
    subs = _count_sub_messages(msg)
    assert subs > 4000

    def decode_whole():  # the two lazy parts are decoded by their first read
        back = cls.decode(raw)
        return back.validator_set, back.signed_header.commit

    calls = _python_calls(decode_whole)
    python_calls = sum(n for name, n in calls.items() if not name.startswith("builtin:"))
    assert python_calls <= MAX_DECODE_CALLS_PER_SUB_MESSAGE * subs, (python_calls, subs)
    assert calls.get("Message.__init__", 0) == 0
    # the reference's own count, so that the ceiling stays a fraction of it
    ref_calls = _python_calls(lambda: ref_decode(cls, raw))
    assert sum(n for name, n in ref_calls.items() if not name.startswith("builtin:")) > 5 * python_calls
    # and what stays unread costs nothing: a header out of the block is a few dozen calls
    header_only = _python_calls(lambda: cls.decode(raw).signed_header.header)
    assert sum(n for name, n in header_only.items() if not name.startswith("builtin:")) < 40


def test_encoding_a_light_block_sorts_nothing(big_messages):
    _, msg = big_messages["LightBlock"]
    raw = msg.encode()
    calls = _python_calls(msg.encode)
    assert calls.get("builtin:sorted", 0) == 0
    assert calls.get("builtin:join", 0) <= _count_sub_messages(msg)  # joined once a message
    assert _python_calls(lambda: ref_encode(msg)).get("builtin:sorted", 0) > 3000  # once a message, as it was
    assert msg.encode() == raw


def test_no_interpretive_path_is_left():
    from tendermint_tpu.proto import message

    for name in ("_decode_field", "_encode_field"):
        assert not hasattr(Message, name)
    for name in ("_decode_scalar", "_encode_scalar"):
        assert not hasattr(message, name)


# -- sign-bytes stay put --------------------------------------------------------


def _golden_cases():
    from test_wire import GOLDEN

    return [pytest.param(chain_id, vote, want, id=f"golden{i}") for i, (chain_id, vote, want) in enumerate(GOLDEN)]


@pytest.mark.parametrize("chain_id, vote, want", _golden_cases())
def test_template_gives_the_golden_sign_bytes(chain_id, vote, want):
    from tendermint_tpu.types.canonical import vote_sign_bytes, vote_sign_bytes_template

    make = vote_sign_bytes_template(chain_id, vote.type, vote.height, vote.round, vote.block_id)
    assert make(vote.timestamp.seconds, vote.timestamp.nanos) == want
    assert vote_sign_bytes(chain_id, vote) == want


@pytest.mark.parametrize("part", ["first_third", "second_third", "last_third"])
def test_sign_bytes_of_a_1000_signature_commit(chain_1k, part):
    from benchmark import reference as ref
    from tendermint_tpu.types.canonical import vote_sign_bytes

    commit = chain_1k.block_store.load_seen_commit(2)
    assert len(commit.signatures) == 1000
    header = commit.block_id.part_set_header
    lo = {"first_third": 0, "second_third": 334, "last_third": 667}[part]
    for idx in range(lo, min(lo + 334, 1000)):
        time_ns = commit.signatures[idx].timestamp.unix_ns()
        want = ref.vote_sign_bytes(chain_1k.chain_id, 2, 0, commit.block_id.hash, header.total, header.hash, time_ns)
        assert commit.vote_sign_bytes(chain_1k.chain_id, idx) == want  # the template
        assert vote_sign_bytes(chain_1k.chain_id, commit.get_vote(idx)) == want  # the whole message
    # and they are what the chain's validators signed
    assert ref.verify(chain_1k.pubkeys[lo], commit.vote_sign_bytes(chain_1k.chain_id, lo), commit.signatures[lo].signature)
