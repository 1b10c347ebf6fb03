"""A validator set is written in one pass.

`ValidatorSet.to_bytes` writes the bytes `to_proto().encode()` writes,
with no `pb` message made: each row is the validator's fixed part
(fields 1-3, built by the codec once and kept on the validator) and its
priority. Held here: that the bytes are the codec's for every shape of
set, whatever was done to it since its last encode, and that a field
written directly after an encode is never served from the memo.
"""

from __future__ import annotations

import hashlib
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tendermint_tpu.crypto.ed25519 import Ed25519PubKey  # noqa: E402
from tendermint_tpu.crypto.secp256k1 import Secp256k1PubKey  # noqa: E402
from tendermint_tpu.crypto.sr25519 import Sr25519PubKey  # noqa: E402
from tendermint_tpu.types.validator_set import Validator, ValidatorSet  # noqa: E402

INT64_MAX = 2**63 - 1


def _seed(tag: str, i: int, n: int = 32) -> bytes:
    return hashlib.sha512(f"{tag}/{i}".encode()).digest()[:n]


def key(i: int, kind: str = "ed25519"):
    if kind == "secp256k1":
        return Secp256k1PubKey(b"\x02" + _seed(kind, i))
    if kind == "sr25519":
        return Sr25519PubKey(_seed(kind, i))
    return Ed25519PubKey(_seed(kind, i))


def validators(n: int, power=lambda i: 10 + i % 7, kinds=("ed25519",)) -> list[Validator]:
    return [Validator.new(key(i, kinds[i % len(kinds)]), power(i)) for i in range(n)]


def encoded(vs: ValidatorSet) -> ValidatorSet:
    """The set after an encode: every row's fixed part kept."""
    vs.to_bytes()
    return vs


def with_priorities(priorities: list[int]) -> ValidatorSet:
    vals = validators(len(priorities))
    for v, p in zip(vals, priorities):
        v.proposer_priority = p
    vs = ValidatorSet(validators=vals)
    vs.proposer = vs._find_proposer()
    return vs


def changed() -> ValidatorSet:
    vs = encoded(ValidatorSet.new(validators(150)))
    leaver, moved = vs.validators[3], vs.validators[40]
    vs.update_with_change_set([
        Validator.new(key(10_000), 25),                          # a join
        Validator(leaver.address, leaver.pub_key, 0),            # a leave
        Validator(moved.address, moved.pub_key, moved.voting_power + 90),  # a power change
    ])
    return vs


def rescaled() -> ValidatorSet:
    vs = encoded(with_priorities([(-1) ** i * 10**12 * (i + 1) for i in range(40)]))
    vs.rescale_priorities(1000)
    return vs


def incremented() -> ValidatorSet:
    vs = encoded(ValidatorSet.new(validators(150)))
    vs.increment_proposer_priority(7)
    return vs


def power_zero_among_others() -> ValidatorSet:
    vals = validators(3)
    vals[1].voting_power = 0
    vals[2].proposer_priority = -3
    return ValidatorSet(validators=vals, proposer=vals[2])


CASES = {
    "empty": lambda: ValidatorSet([]),
    "proposer_none": lambda: ValidatorSet(validators=validators(5)),
    "n1": lambda: ValidatorSet.new(validators(1)),
    "n4": lambda: ValidatorSet.new(validators(4)),
    "n150": lambda: ValidatorSet.new(validators(150)),
    "n1000": lambda: ValidatorSet.new(validators(1000)),
    "mixed_key_types": lambda: ValidatorSet.new(
        validators(30, kinds=("ed25519", "secp256k1", "sr25519"))),
    "priorities_zero_negative_and_int64_ends": lambda: with_priorities(
        [0, -1, 1, -127, -128, 128, -(2**40), INT64_MAX, -INT64_MAX, 0, -(2**63)]),
    "power_zero_row": lambda: ValidatorSet(validators=[Validator.new(key(1), 0)]),
    "power_zero_row_among_others": power_zero_among_others,
    "after_increment_proposer_priority": incremented,
    "after_update_with_change_set": changed,
    "after_rescale_priorities": rescaled,
    "copy": lambda: encoded(incremented()).copy(),
    "from_bytes": lambda: ValidatorSet.from_bytes(changed().to_proto().encode()),
    "from_bytes_mixed_key_types": lambda: ValidatorSet.from_bytes(ValidatorSet.new(
        validators(12, kinds=("secp256k1", "sr25519", "ed25519"))).to_proto().encode()),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_to_bytes_is_the_codecs_encoding(case):
    vs = CASES[case]()
    want = vs.to_proto().encode()
    first, rows, _ = vs.encode_counted()
    assert first == want
    assert rows == len(vs.validators) + (vs.proposer is not None)
    # again, every fixed part now from the memo
    again, rows_again, kept = vs.encode_counted()
    assert again == want and (rows_again, kept) == (rows, rows)
    if case == "empty":
        assert want == b""
    assert ValidatorSet.from_bytes(again).to_bytes() == want


def _replace_pub_key(v: Validator) -> None:
    v.pub_key = key(99_999)


def _replace_pub_key_with_an_equal_one(v: Validator) -> None:
    v.pub_key = Ed25519PubKey(v.pub_key.bytes())


def _replace_voting_power(v: Validator) -> None:
    v.voting_power += 1000


def _replace_address(v: Validator) -> None:
    v.address = bytes(20)


@pytest.mark.parametrize("write", [_replace_pub_key, _replace_pub_key_with_an_equal_one,
                                   _replace_voting_power, _replace_address],
                         ids=lambda f: f.__name__.lstrip("_"))
@pytest.mark.parametrize("which", ["a_validator", "the_proposer"])
def test_a_field_written_after_an_encode_is_not_served_from_the_memo(write, which):
    vs = encoded(ValidatorSet.new(validators(40)))
    v = vs.proposer if which == "the_proposer" else vs.validators[17]
    if which == "a_validator" and v is vs.proposer:
        v = vs.validators[18]
    write(v)
    got, rows, kept = vs.encode_counted()
    assert got == vs.to_proto().encode()
    assert kept == rows - 1  # the one row written again, every other kept
    back = ValidatorSet.from_bytes(got)
    assert back.validators == vs.validators and back.proposer == vs.proposer
    # not `got`: a power written directly leaves vs's memoized total as it was
    assert back.to_bytes() == back.to_proto().encode()
