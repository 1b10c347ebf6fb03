"""The plain reference for a chain whose validator set rotates: which
set signs which height, a set's hash, and the two verdicts of a light
client's trust step when the trusted set and the signing set differ.

Beside `benchmark/reference.py`, on which it builds, and like it
importing nothing of tendermint_tpu. The schedule is written here from
the configuration's `rotation` rule alone (Go Tendermint: a validator
update returned by block H acts from H + 2, `state/execution.go`
updateState; a set is ordered by power descending, then address,
`types/validator_set.go` ValidatorsByVotingPower), never read from the
program, so that a program that rotates at another height, orders a set
otherwise or hashes it otherwise differs from it.
"""

from __future__ import annotations

import hashlib

from benchmark import reference as ref


def address(pubkey: bytes) -> bytes:
    """crypto/ed25519 PubKey.Address: the first 20 bytes of SHA-256."""
    return hashlib.sha256(pubkey).digest()[:20]


def simple_validator(pubkey: bytes, power: int) -> bytes:
    """types/validator.go Validator.Bytes: SimpleValidator{pub_key:
    PublicKey{ed25519 = 1}, voting_power = 2}, proto-encoded."""
    return ref._field_bytes(1, ref._field_bytes(1, pubkey, always=True)) + ref._field_varint(2, power)


def validator_set_hash(validators: list[tuple[bytes, int]]) -> bytes:
    """types/validator_set.go ValidatorSet.Hash over (pubkey, power) in set order."""
    return ref.merkle_root([simple_validator(pk, power) for pk, power in validators])


def in_set_order(validators: list[tuple[bytes, int]]) -> list[tuple[bytes, int]]:
    return sorted(validators, key=lambda v: (-v[1], address(v[0])))


class Schedule:
    """The set at every height of a chain that starts with `validators`
    keys of equal `power` and in which every block makes the
    longest-serving key leave and a key never seen join: keys[0:n] at
    genesis, block H removing keys[H - 1] and adding keys[n + H - 1],
    each change acting from height H + 2."""

    def __init__(self, pubkeys: list[bytes], validators: int, power: int, per_block: int = 1):
        self.pubkeys, self.n, self.power, self.per_block = pubkeys, validators, power, per_block
        self._sets: dict[int, list[tuple[bytes, int]]] = {}
        self._hashes: dict[int, bytes] = {}

    def rotated_before(self, height: int) -> int:
        """Keys that have left by `height`: the changes of blocks 1 .. height - 2."""
        return max(0, height - 2) * self.per_block

    def set_at(self, height: int) -> list[tuple[bytes, int]]:
        """(pubkey, power) in set order, the order of a commit's signatures."""
        if height not in self._sets:
            first = self.rotated_before(height)
            self._sets[height] = in_set_order(
                [(pk, self.power) for pk in self.pubkeys[first: first + self.n]])
        return self._sets[height]

    def hash_at(self, height: int) -> bytes:
        if height not in self._hashes:
            self._hashes[height] = validator_set_hash(self.set_at(height))
        return self._hashes[height]


def trusting_rows(trusted: list[tuple[bytes, int]], signers: list[bytes],
                  signed: list[bool], num: int = 1, den: int = 3) -> tuple[bool, list[int]]:
    """types/validation.go VerifyCommitLightTrusting's walk, without
    the signatures: a commit's rows in order, each signer looked up by
    address in the trusted set, those not in it passed over, stopping
    once more than num/den of the trusted power is tallied. Returns
    (reached, the commit rows whose signatures the check verifies)."""
    powers = {address(pk): power for pk, power in trusted}
    needed = sum(powers.values()) * num // den
    tallied, rows = 0, []
    for row, (pk, did_sign) in enumerate(zip(signers, signed)):
        power = powers.get(address(pk))
        if not did_sign or power is None:
            continue
        rows.append(row)
        tallied += power
        if tallied > needed:
            return True, rows
    return False, rows


def light_rows(signing: list[tuple[bytes, int]], signed: list[bool],
               num: int = 2, den: int = 3) -> tuple[bool, list[int]]:
    """VerifyCommitLight's walk: rows by index in the signing set,
    stopping once more than num/den of its power is tallied."""
    needed = sum(power for _, power in signing) * num // den
    tallied, rows = 0, []
    for row, ((_, power), did_sign) in enumerate(zip(signing, signed)):
        if not did_sign:
            continue
        rows.append(row)
        tallied += power
        if tallied > needed:
            return True, rows
    return False, rows


def step_verdicts(trusted: list[tuple[bytes, int]], signing: list[tuple[bytes, int]],
                  sigs: list[bytes | None], msgs: list[bytes],
                  verify_fn=ref.verify) -> tuple[bool, bool]:
    """The two checks of a non-adjacent trust step over plain values:
    (more than 1/3 of the TRUSTED set's power signed this commit, by
    address, every signature that walk reaches verifying; more than 2/3
    of the commit's own set did, by index: `reference.commit_verdict`)."""
    signers = [pk for pk, _ in signing]
    reached, rows = trusting_rows(trusted, signers, [sig is not None for sig in sigs])
    trusting = reached and all(verify_fn(signers[r], msgs[r], sigs[r]) for r in rows)
    own, _ = ref.commit_verdict(signers, [power for _, power in signing], sigs, msgs, 2, 3, True,
                                verify_fn)
    return trusting, own
