"""Evidence of Byzantine behavior (ref: types/evidence.go)."""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field

from ..proto import messages as pb
from ..proto import wire
from ..utils.tmtime import Time
from .validator_set import Validator, ValidatorSet
from .vote import Vote

HASH_SIZE = 32


@dataclass
class DuplicateVoteEvidence:
    """Two conflicting votes from one validator (ref: types/evidence.go:41)."""

    vote_a: Vote
    vote_b: Vote
    total_voting_power: int = 0
    validator_power: int = 0
    timestamp: Time = field(default_factory=Time)

    @classmethod
    def new(cls, vote_a: Vote, vote_b: Vote, block_time: Time, val_set: ValidatorSet) -> "DuplicateVoteEvidence":
        """Orders the votes lexically by BlockID key (ref: NewDuplicateVoteEvidence,
        types/evidence.go:60)."""
        if vote_a is None or vote_b is None or val_set is None:
            raise ValueError("missing vote or validator set")
        _, val = val_set.get_by_address(vote_a.validator_address)
        if val is None:
            raise ValueError("validator not in validator set")
        if vote_a.block_id.key() < vote_b.block_id.key():
            first, second = vote_a, vote_b
        else:
            first, second = vote_b, vote_a
        return cls(
            vote_a=first,
            vote_b=second,
            total_voting_power=val_set.total_voting_power(),
            validator_power=val.voting_power,
            timestamp=block_time,
        )

    def abci_height(self) -> int:
        return self.vote_a.height

    def generate_abci(self, val: Validator, val_set: ValidatorSet, evidence_time: Time) -> None:
        """Populate the ABCI component (ref: GenerateABCI, types/evidence.go:184)."""
        self.validator_power = val.voting_power
        self.total_voting_power = val_set.total_voting_power()
        self.timestamp = evidence_time

    @property
    def height(self) -> int:
        return self.vote_a.height

    @property
    def time(self) -> Time:
        return self.timestamp

    def bytes(self) -> bytes:
        return self.to_proto().encode()

    def hash(self) -> bytes:
        return hashlib.sha256(self.bytes()).digest()

    def validate_basic(self) -> None:
        """ref: DuplicateVoteEvidence.ValidateBasic (types/evidence.go:152)."""
        if self.vote_a is None or self.vote_b is None:
            raise ValueError("empty duplicate vote")
        self.vote_a.validate_basic()
        self.vote_b.validate_basic()
        if self.vote_a.block_id.key() >= self.vote_b.block_id.key():
            raise ValueError("duplicate votes in invalid order")

    def to_proto(self) -> pb.DuplicateVoteEvidence:
        return pb.DuplicateVoteEvidence(
            vote_a=self.vote_a.to_proto(),
            vote_b=self.vote_b.to_proto(),
            total_voting_power=self.total_voting_power,
            validator_power=self.validator_power,
            timestamp=pb.Timestamp(seconds=self.timestamp.seconds, nanos=self.timestamp.nanos),
        )

    @classmethod
    def from_proto(cls, p: pb.DuplicateVoteEvidence) -> "DuplicateVoteEvidence":
        t = p.timestamp or pb.Timestamp()
        return cls(
            vote_a=Vote.from_proto(p.vote_a),
            vote_b=Vote.from_proto(p.vote_b),
            total_voting_power=p.total_voting_power or 0,
            validator_power=p.validator_power or 0,
            timestamp=Time(t.seconds or 0, t.nanos or 0) if (t.seconds or t.nanos) else Time(),
        )


@dataclass
class LightClientAttackEvidence:
    """A conflicting light block trace (ref: types/evidence.go:259)."""

    conflicting_block: "LightBlock"
    common_height: int = 0
    byzantine_validators: list[Validator] = field(default_factory=list)
    total_voting_power: int = 0
    timestamp: Time = field(default_factory=Time)

    @property
    def height(self) -> int:
        """The common height — the infraction height for expiry purposes
        (ref: types/evidence.go:386)."""
        return self.common_height

    @property
    def time(self) -> Time:
        return self.timestamp

    def bytes(self) -> bytes:
        return self.to_proto().encode()

    def hash(self) -> bytes:
        """ref: LightClientAttackEvidence.Hash (types/evidence.go:374).
        Fixed-size buffer semantics: a short header hash leaves zero bytes,
        exactly like Go's copy into a preallocated array."""
        varint = wire.encode_zigzag(self.common_height)
        bz = bytearray(HASH_SIZE + len(varint))
        conflicting_hash = (self.conflicting_block.signed_header.header.hash() or b"")[: HASH_SIZE - 1]
        bz[: len(conflicting_hash)] = conflicting_hash
        bz[HASH_SIZE:] = varint
        return hashlib.sha256(bytes(bz)).digest()

    def conflicting_header_is_invalid(self, trusted_header) -> bool:
        """Whether this was a lunatic attack (ref: ConflictingHeaderIsInvalid,
        types/evidence.go:310)."""
        h = self.conflicting_block.signed_header.header
        return (
            trusted_header.validators_hash != h.validators_hash
            or trusted_header.next_validators_hash != h.next_validators_hash
            or trusted_header.consensus_hash != h.consensus_hash
            or trusted_header.app_hash != h.app_hash
            or trusted_header.last_results_hash != h.last_results_hash
        )

    def get_byzantine_validators(self, common_vals: ValidatorSet, trusted) -> list[Validator]:
        """Work out which validators were malicious depending on attack style
        (ref: GetByzantineValidators, types/evidence.go:305-344). `trusted`
        is the trusted SignedHeader (commit needed for the equivocation
        round comparison). Output ordered by descending voting power."""
        from .validator_set import _sort_by_voting_power

        byzantine: list[Validator] = []
        if self.conflicting_header_is_invalid(trusted.header):
            # Lunatic attack: common-set validators who signed the
            # conflicting (lunatic) header.
            commit = self.conflicting_block.signed_header.commit
            for sig in commit.signatures:
                if not sig.for_block():
                    continue
                _, val = common_vals.get_by_address(sig.validator_address)
                if val is not None:
                    byzantine.append(val)
            _sort_by_voting_power(byzantine)
            return byzantine
        if trusted.commit.round == self.conflicting_block.signed_header.commit.round:
            # Equivocation: both commits in the same round — validators
            # that voted in BOTH headers. Validator hashes match, so the
            # index order is shared and one indexed loop suffices.
            sigs_a = self.conflicting_block.signed_header.commit.signatures
            sigs_b = trusted.commit.signatures
            for i, sig_a in enumerate(sigs_a):
                if not sig_a.for_block():
                    continue
                if i >= len(sigs_b) or not sigs_b[i].for_block():
                    continue
                _, val = self.conflicting_block.validator_set.get_by_address(sig_a.validator_address)
                if val is not None:
                    byzantine.append(val)
            _sort_by_voting_power(byzantine)
            return byzantine
        # Different rounds: amnesia attack — not attributable (ref :341).
        return byzantine

    def generate_abci(self, common_vals: ValidatorSet, trusted, evidence_time: Time) -> None:
        """Populate the ABCI component (ref: GenerateABCI, types/evidence.go:497)."""
        self.byzantine_validators = self.get_byzantine_validators(common_vals, trusted)
        self.total_voting_power = common_vals.total_voting_power()
        self.timestamp = evidence_time

    def validate_basic(self) -> None:
        if self.conflicting_block is None or self.conflicting_block.signed_header is None:
            raise ValueError("conflicting block missing header")
        try:
            self.conflicting_block.validate_basic(self.conflicting_block.signed_header.header.chain_id)
        except ValueError as e:
            raise ValueError(f"invalid conflicting light block: {e}") from e
        if self.common_height <= 0:
            raise ValueError("negative or zero common height")
        if self.common_height > self.conflicting_block.signed_header.header.height:
            raise ValueError("common height has to be less than equal to the conflicting block height")
        if self.total_voting_power <= 0:
            raise ValueError("negative or zero total voting power")

    def to_proto(self) -> pb.LightClientAttackEvidence:
        return pb.LightClientAttackEvidence(
            conflicting_block=self.conflicting_block.to_proto(),
            common_height=self.common_height,
            byzantine_validators=[v.to_proto() for v in self.byzantine_validators],
            total_voting_power=self.total_voting_power,
            timestamp=pb.Timestamp(seconds=self.timestamp.seconds, nanos=self.timestamp.nanos),
        )

    @classmethod
    def from_proto(cls, p: pb.LightClientAttackEvidence) -> "LightClientAttackEvidence":
        from .light_block import LightBlock

        t = p.timestamp or pb.Timestamp()
        conflicting = LightBlock.from_proto(p.conflicting_block)
        # Evidence is read in full wherever it is verified, and a block
        # can carry it past the pool's check (pending, matched by the
        # header's hash): a part that is no message is refused here, at
        # decoding, not where it is first read.
        conflicting.read_parts()
        return cls(
            conflicting_block=conflicting,
            common_height=p.common_height or 0,
            byzantine_validators=[Validator.from_proto(v) for v in (p.byzantine_validators or [])],
            total_voting_power=p.total_voting_power or 0,
            timestamp=Time(t.seconds or 0, t.nanos or 0) if (t.seconds or t.nanos) else Time(),
        )


Evidence = DuplicateVoteEvidence | LightClientAttackEvidence


def evidence_to_proto(ev: Evidence) -> pb.Evidence:
    """ref: types/evidence.go EvidenceToProto."""
    if isinstance(ev, DuplicateVoteEvidence):
        return pb.Evidence(duplicate_vote_evidence=ev.to_proto())
    if isinstance(ev, LightClientAttackEvidence):
        return pb.Evidence(light_client_attack_evidence=ev.to_proto())
    raise TypeError(f"evidence is not recognized: {type(ev)}")


def evidence_from_proto(p: pb.Evidence) -> Evidence:
    if p.duplicate_vote_evidence is not None:
        return DuplicateVoteEvidence.from_proto(p.duplicate_vote_evidence)
    if p.light_client_attack_evidence is not None:
        return LightClientAttackEvidence.from_proto(p.light_client_attack_evidence)
    raise ValueError("evidence is not recognized")


def evidence_to_abci(evidence: list) -> list:
    """Convert evidence to ABCI Misbehavior records
    (ref: EvidenceList.ToABCI / Evidence.ABCI(), types/evidence.go:70,300)."""
    from ..abci import types as abci

    out = []
    for ev in evidence:
        if isinstance(ev, DuplicateVoteEvidence):
            out.append(
                abci.Misbehavior(
                    type=abci.MISBEHAVIOR_DUPLICATE_VOTE,
                    validator=abci.Validator(address=ev.vote_a.validator_address, power=ev.validator_power),
                    height=ev.vote_a.height,
                    time_ns=ev.timestamp.unix_ns(),
                    total_voting_power=ev.total_voting_power,
                )
            )
        elif isinstance(ev, LightClientAttackEvidence):
            for val in ev.byzantine_validators:
                out.append(
                    abci.Misbehavior(
                        type=abci.MISBEHAVIOR_LIGHT_CLIENT_ATTACK,
                        validator=abci.Validator(address=val.address, power=val.voting_power),
                        height=ev.common_height,
                        time_ns=ev.timestamp.unix_ns(),
                        total_voting_power=ev.total_voting_power,
                    )
                )
        else:
            raise TypeError(f"evidence is not recognized: {type(ev)}")
    return out
