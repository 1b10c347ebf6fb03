"""Canonical sign-bytes construction (ref: types/canonical.go, types/vote.go:149).

The byte layout here is the contract the TPU verifier checks signatures
over; it is golden-tested against the reference's types/vote_test.go
vectors and must never drift.
"""

from __future__ import annotations

from ..proto import messages as pb
from ..proto import wire


def canonicalize_block_id(bid: pb.BlockID | None) -> pb.CanonicalBlockID | None:
    """Nil/empty block IDs canonicalize to an absent field
    (ref: types/canonical.go:18-34)."""
    if bid is None:
        return None
    psh = bid.part_set_header or pb.PartSetHeader()
    is_zero = not bid.hash and not psh.hash and not psh.total
    if is_zero:
        return None
    return pb.CanonicalBlockID(
        hash=bid.hash,
        part_set_header=pb.CanonicalPartSetHeader(total=psh.total, hash=psh.hash),
    )


def canonicalize_vote(chain_id: str, vote: pb.Vote) -> pb.CanonicalVote:
    return pb.CanonicalVote(
        type=vote.type,
        height=vote.height,
        round=vote.round,
        block_id=canonicalize_block_id(vote.block_id),
        timestamp=vote.timestamp.copy() if vote.timestamp else pb.Timestamp(),
        chain_id=chain_id,
    )


def canonicalize_proposal(chain_id: str, proposal: pb.Proposal) -> pb.CanonicalProposal:
    return pb.CanonicalProposal(
        type=pb.SIGNED_MSG_TYPE_PROPOSAL,
        height=proposal.height,
        round=proposal.round,
        pol_round=proposal.pol_round,
        block_id=canonicalize_block_id(proposal.block_id),
        timestamp=proposal.timestamp.copy() if proposal.timestamp else pb.Timestamp(),
        chain_id=chain_id,
    )


def canonicalize_vote_extension(chain_id: str, vote: pb.Vote) -> pb.CanonicalVoteExtension:
    return pb.CanonicalVoteExtension(
        extension=vote.extension,
        height=vote.height,
        round=vote.round,
        chain_id=chain_id,
    )


def vote_sign_bytes(chain_id: str, vote: pb.Vote) -> bytes:
    """Varint-length-prefixed canonical vote encoding
    (ref: types/vote.go:149 VoteSignBytes)."""
    return canonicalize_vote(chain_id, vote).encode_delimited()


def vote_extension_sign_bytes(chain_id: str, vote: pb.Vote) -> bytes:
    return canonicalize_vote_extension(chain_id, vote).encode_delimited()


def proposal_sign_bytes(chain_id: str, proposal: pb.Proposal) -> bytes:
    return canonicalize_proposal(chain_id, proposal).encode_delimited()


def vote_sign_bytes_template(chain_id: str, type_: int, height: int, round_: int, block_id: pb.BlockID | None):
    """Prefix/suffix split of the canonical vote encoding around the
    timestamp field (the only per-validator variation inside one
    commit): returns make(seconds, nanos) -> sign bytes.

    Byte-identical to `vote_sign_bytes` — the template reuses the exact
    field encoders — but skips the per-call proto object graph, which
    dominates at 10k-validator commit scale (types/validation.py's
    batch loop). Parity is pinned by tests/test_types.py.
    """
    encode_field = pb.CanonicalVote.encode_field
    prefix = b"".join(
        encode_field(name, value)
        for name, value in (
            ("type", type_),
            ("height", height),
            ("round", round_),
            ("block_id", canonicalize_block_id(block_id)),
        )
    )
    suffix = encode_field("chain_id", chain_id)
    ts_tag = b"\x2a"  # CanonicalVote.timestamp: field 5, length-delimited
    encode_varint = wire.encode_varint

    def make(seconds: int, nanos: int) -> bytes:
        tsb = b""
        if seconds:
            tsb += b"\x08" + encode_varint(seconds)
        if nanos:
            tsb += b"\x10" + encode_varint(nanos)
        body = prefix + ts_tag + encode_varint(len(tsb)) + tsb + suffix
        return encode_varint(len(body)) + body

    return make
