"""SignedHeader and LightBlock (ref: types/light.go).

A light block's two large parts, the commit and the validator set, are
built the first time something reads them (`_Deferred`): `from_proto`
builds the header alone. A block that is only compared by its header's
hash (a witness's copy, light/client.py `_cross_reference`) never pays
for the parts; a block that is validated, verified or stored reads both
in `validate_basic` and is an ordinary object from then on.

A part that is read is decoded once. Where it still lies in the buffer
the block came in (proto/message.py `lazy`), `Commit.from_bytes` and
`ValidatorSet.from_bytes` go from those bytes to the objects in one
pass: no `pb.Commit` or `pb.ValidatorSet` is made on the way. A
validator built so keeps its merkle leaf from that pass, and the leaf
is built from the values decoded (the key, the power), not copied from
the peer's bytes: `validate_basic` hashes the same leaves and holds the
root to the header as before. A part that is already a `pb` message (a
block made in this process) goes through `from_proto`.
"""

from __future__ import annotations

from dataclasses import dataclass

from .. import trace as _trace
from ..metrics import light_metrics as _light_metrics
from ..proto import messages as pb
from ..proto.message import Deferred, DeferredAttr, _Unread, held
from .block import Commit, Header
from .validator_set import ValidatorSet


class _Deferred(Deferred):
    """A part not yet built: the proto message that carries it (whose
    own field may still be bytes, proto/message.py `lazy`) and the class
    (`Commit`, `ValidatorSet`) that builds it."""

    __slots__ = ("source", "part", "cls")

    def __init__(self, source, part: str, cls):
        self.source, self.part, self.cls = source, part, cls
        _light_metrics().block_parts.add(1, part, "deferred")

    def read(self):
        """Build the part: straight from its bytes where the message has
        not decoded them (`path="direct"`), from the `pb` message where
        it holds one (`path="message"`). Malformed bytes raise the
        ValueError that decoding the whole block used to raise. A part
        the message does not carry reads as None, which `validate_basic`
        refuses."""
        part = self.part
        with _trace.span("light.decode_part", "light", part=part):
            p = held(self.source, part)
            if p.__class__ is _Unread:
                path, value = "direct", self.cls.from_bytes(p.buf, p.start, p.end)
            else:
                path, value = "message", None if p is None else self.cls.from_proto(p)
            rows = 0 if value is None else value.size()
            _trace.annotate(path=path, rows=rows)
        metrics = _light_metrics()
        metrics.block_parts.add(1, part, "read")
        metrics.part_rows.add(rows, part, path)
        return value


@dataclass
class SignedHeader:
    header: Header
    commit: Commit = DeferredAttr("commit")

    def validate_basic(self, chain_id: str) -> None:
        """ref: SignedHeader.ValidateBasic (types/light.go:161)."""
        if self.header is None:
            raise ValueError("missing header")
        if self.commit is None:
            raise ValueError("missing commit")
        self.header.validate_basic()
        self.commit.validate_basic()
        if self.header.chain_id != chain_id:
            raise ValueError(f"header belongs to another chain {self.header.chain_id!r}, not {chain_id!r}")
        if self.commit.height != self.header.height:
            raise ValueError(f"header and commit height mismatch: {self.header.height} vs {self.commit.height}")
        hhash = self.header.hash() or b""
        chash = self.commit.block_id.hash
        if hhash != chash:
            raise ValueError(f"commit signs block {chash.hex()}, header is block {hhash.hex()}")

    @property
    def height(self) -> int:
        return self.header.height

    def hash(self) -> bytes | None:
        return self.header.hash()

    def to_proto(self) -> pb.SignedHeader:
        return pb.SignedHeader(header=self.header.to_proto(), commit=self.commit.to_proto())

    @classmethod
    def from_proto(cls, p: pb.SignedHeader) -> "SignedHeader":
        return cls(header=Header.from_proto(p.header), commit=_Deferred(p, "commit", Commit))


@dataclass
class LightBlock:
    """SignedHeader + the validator set that signed it (ref: types/light.go:14)."""

    signed_header: SignedHeader
    validator_set: ValidatorSet = DeferredAttr("validator_set")

    @property
    def height(self) -> int:
        return self.signed_header.header.height

    def validate_basic(self, chain_id: str) -> None:
        """ref: LightBlock.ValidateBasic (types/light.go:55)."""
        if self.signed_header is None:
            raise ValueError("missing signed header")
        if self.validator_set is None:
            raise ValueError("missing validator set")
        self.signed_header.validate_basic(chain_id)
        self.validator_set.validate_basic()
        if self.signed_header.header.validators_hash != self.validator_set.hash():
            raise ValueError(
                f"expected validator hash of header to match validator set hash "
                f"({self.signed_header.header.validators_hash.hex()} != {self.validator_set.hash().hex()})"
            )

    def read_parts(self) -> None:
        """Decode and build both parts now, for a caller that must know
        here, and not where it first reads one, that they are messages:
        raises the ValueError the read of a malformed part raises."""
        self.signed_header.commit, self.validator_set  # noqa: B018

    def to_proto(self) -> pb.LightBlock:
        return pb.LightBlock(signed_header=self.signed_header.to_proto(), validator_set=self.validator_set.to_proto())

    @classmethod
    def from_proto(cls, p: pb.LightBlock) -> "LightBlock":
        return cls(
            signed_header=SignedHeader.from_proto(p.signed_header),
            validator_set=_Deferred(p, "validator_set", ValidatorSet),
        )
