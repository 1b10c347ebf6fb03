"""The two checks of a light client's non-adjacent step, walked first
and submitted to the engine together (types/validation.py
verify_commit_light_trusting held, then
verify_commit_light_after_trusting), against the two blocking functions
called one after the other: the same exception type and message in the
same order, and the engine handed what the case says, a refused jump
nothing at all.
"""

import pytest

from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
from tendermint_tpu.crypto.secp256k1 import Secp256k1PrivKey
from tendermint_tpu.ops import engine as E
from tendermint_tpu.types import (
    BlockID,
    Commit,
    CommitSig,
    Fraction,
    NotEnoughVotingPowerError,
    PartSetHeader,
    Validator,
    ValidatorSet,
    Vote,
    verify_commit_light,
    verify_commit_light_trusting,
)
from tendermint_tpu.types import PRECOMMIT
from tendermint_tpu.types.validation import verify_commit_light_after_trusting
from tendermint_tpu.utils.tmtime import Time

CHAIN_ID = "pair-chain"
HEIGHT = 7
THIRD = Fraction(1, 3)
BLOCK_ID = BlockID(hash=b"\xaa" * 32, part_set_header=PartSetHeader(total=1, hash=b"\xbb" * 32))
TS = Time.parse_rfc3339("2024-01-02T03:04:05Z")


@pytest.fixture(autouse=True)
def _host_route(monkeypatch):
    # what is compared is the walk, the submission and the order of the
    # verdicts, not the kernel: the engine's host plane keeps it quick
    monkeypatch.setenv("TM_TPU_CRYPTO", "off")


@pytest.fixture
def submissions(monkeypatch):
    """How many batches each call into the engine brought."""
    calls = []
    real = E.VerifyEngine.submit_together

    def spy(self, batches):
        batches = list(batches)
        calls.append(len(batches))
        return real(self, batches)

    monkeypatch.setattr(E.VerifyEngine, "submit_together", spy)
    return calls


def ed_keys(n, tag=0):
    return [Ed25519PrivKey.generate(bytes([tag, i + 1]) * 16) for i in range(n)]


def make_set(keys, power=10):
    """The set, and its keys in the set's order."""
    vset = ValidatorSet.new([Validator.new(k.pub_key(), power) for k in keys])
    by_addr = {k.pub_key().address(): k for k in keys}
    return vset, [by_addr[v.address] for v in vset.validators]


def make_commit(vset, keys, absent=(), block_id=BLOCK_ID, height=HEIGHT):
    sigs = []
    for i, (val, key) in enumerate(zip(vset.validators, keys)):
        if i in absent:
            sigs.append(CommitSig.new_absent())
            continue
        vote = Vote(type=PRECOMMIT, height=height, round=0, block_id=block_id, timestamp=TS,
                    validator_address=val.address, validator_index=i)
        sigs.append(CommitSig(block_id_flag=2, validator_address=val.address, timestamp=TS,
                              signature=key.sign(vote.sign_bytes(CHAIN_ID))))
    return Commit(height=height, round=0, block_id=block_id, signatures=sigs)


def spoil(commit, *rows):
    for row in rows:
        cs = commit.signatures[row]
        commit.signatures[row] = CommitSig(
            block_id_flag=cs.block_id_flag, validator_address=cs.validator_address,
            timestamp=cs.timestamp, signature=bytes([cs.signature[0] ^ 1]) + cs.signature[1:])


class Case:
    """Twelve validators of power 10 sign. Against an unchanged trusted
    set the trusting batch is rows 0-4 (50 > 40) and the light batch
    rows 0-8 (90 > 80). The `moved` trusted set holds rows 6-11 of the
    signers and six strangers: by address the trusting batch is rows
    6-10, no prefix of the light batch, and rows 0-5 are not in it."""

    def __init__(self):
        self.vals, self.keys = make_set(ed_keys(12))
        self.trusted = self.vals
        self.commit = make_commit(self.vals, self.keys)
        self.block_id, self.height = BLOCK_ID, HEIGHT
        self.light_vals = self.vals

    def moved(self):
        self.trusted, _ = make_set(self.keys[6:] + ed_keys(6, tag=9))
        return self

    def strangers(self):
        self.trusted, _ = make_set(self.keys[:3] + ed_keys(9, tag=9))  # 30 of 120: short of a third
        return self


def sound(c):
    pass


def unsound_trusting(c):
    spoil(c.commit, 3)


def mixed_keys():
    """Eleven ed25519 keys and one secp256k1 key that the set's order
    puts inside both batches and not first, so that an ed25519
    proposer's batch refuses it and the walk falls back."""
    for seed in range(64):
        keys = ed_keys(11) + [Secp256k1PrivKey.generate(bytes([seed]) * 8)]
        vset, ordered = make_set(keys)
        row = next(i for i, k in enumerate(ordered) if isinstance(k, Secp256k1PrivKey))
        if 1 <= row <= 3 and vset.get_proposer().pub_key.type_name == "ed25519":
            return vset, ordered, row
    raise AssertionError("no seed puts the secp256k1 key in rows 1-3")


CASES = {}


def case(name, jobs):
    """`jobs`: what the engine is handed, a call at a time."""
    def add(build):
        CASES[name] = (build, jobs)
        return build
    return add


@case("all_sound", [2])
def _():
    return Case()


@case("trusting_power_short", [])
def _():
    return Case().strangers()


@case("bad_signature_in_the_trusting_rows_only", [2])
def _():
    c = Case().moved()
    spoil(c.commit, 10)
    return c


@case("bad_signature_in_the_light_tail_only", [2])
def _():
    c = Case()
    spoil(c.commit, 7)
    return c


@case("bad_signature_in_both_and_the_trusting_index_wins", [2])
def _():
    c = Case().moved()
    spoil(c.commit, 2, 9)  # the light batch alone would name #2
    return c


@case("bad_signature_in_a_row_both_batches_hold", [2])
def _():
    c = Case()
    spoil(c.commit, 3)
    return c


@case("set_changed_signers_missing_all_sound", [2])
def _():
    return Case().moved()


def light_walk_fails(name, break_light):
    for trusting_name, break_trusting in (("sound", sound), ("unsound", unsound_trusting)):
        @case(f"light_{name}_trusting_{trusting_name}", [1])
        def _(break_light=break_light, break_trusting=break_trusting):
            c = Case()
            break_light(c)
            break_trusting(c)
            return c


def wrong_block_id(c):
    c.block_id = BlockID(hash=b"\xcc" * 32, part_set_header=BLOCK_ID.part_set_header)


def wrong_height(c):
    c.height = HEIGHT + 1


def wrong_set_size(c):
    c.light_vals, _ = make_set(c.keys[:11])


def power_short(c):
    # rows 0-6 sign, 70 of 120: more than a third, not more than two thirds
    c.commit = make_commit(c.vals, c.keys, absent=range(7, 12))


light_walk_fails("wrong_block_id", wrong_block_id)
light_walk_fails("wrong_height", wrong_height)
light_walk_fails("wrong_set_size", wrong_set_size)
light_walk_fails("power_short", power_short)


@case("mixed_keys_fall_back_to_serial_in_each_check", [])
def _():
    c = Case()
    c.vals, c.keys, _ = mixed_keys()
    c.trusted = c.light_vals = c.vals
    c.commit = make_commit(c.vals, c.keys)
    return c


@case("mixed_keys_bad_signature", [])
def _():
    c = Case()
    c.vals, c.keys, row = mixed_keys()
    c.trusted = c.light_vals = c.vals
    c.commit = make_commit(c.vals, c.keys)
    spoil(c.commit, row + 1)
    return c


@case("mixed_keys_in_the_light_set_only", [1])
def _():
    c = Case()
    c.vals, c.keys, _ = mixed_keys()
    c.light_vals = c.vals
    c.commit = make_commit(c.vals, c.keys)
    c.trusted, _ = make_set([k for k in c.keys if isinstance(k, Ed25519PrivKey)])
    return c


def one_after_the_other(c):
    verify_commit_light_trusting(CHAIN_ID, c.trusted, c.commit, THIRD)
    verify_commit_light(CHAIN_ID, c.light_vals, c.block_id, c.height, c.commit)


def walked_then_together(c):
    trusting = verify_commit_light_trusting(CHAIN_ID, c.trusted, c.commit, THIRD, hold=True)
    verify_commit_light_after_trusting(
        trusting, CHAIN_ID, c.light_vals, c.block_id, c.height, c.commit)


def raised(check, c):
    try:
        check(c)
    except Exception as e:  # noqa: BLE001 - the error surface is what is compared
        return type(e), str(e)
    return None


@pytest.mark.parametrize("name", list(CASES))
def test_the_pair_raises_what_the_two_blocking_checks_raise_in_their_order(name, submissions):
    build, jobs = CASES[name]
    want = raised(one_after_the_other, build())
    del submissions[:]
    got = raised(walked_then_together, build())
    assert got == want
    assert submissions == jobs


def test_the_cases_refuse_for_the_reason_their_names_give():
    """The oracle's own verdicts, so that a case cannot pass by both
    sides failing early in the same wrong place."""
    want = {
        "all_sound": None,
        "trusting_power_short": (NotEnoughVotingPowerError, "got 30, needed more than 40"),
        "bad_signature_in_the_trusting_rows_only": (ValueError, "wrong signature (#10)"),
        "bad_signature_in_the_light_tail_only": (ValueError, "wrong signature (#7)"),
        "bad_signature_in_both_and_the_trusting_index_wins": (ValueError, "wrong signature (#9)"),
        "bad_signature_in_a_row_both_batches_hold": (ValueError, "wrong signature (#3)"),
        "set_changed_signers_missing_all_sound": None,
        "light_wrong_block_id_trusting_sound": (ValueError, "wrong block ID"),
        "light_wrong_block_id_trusting_unsound": (ValueError, "wrong signature (#3)"),
        "light_wrong_height_trusting_sound": (ValueError, "wrong height"),
        "light_wrong_height_trusting_unsound": (ValueError, "wrong signature (#3)"),
        "light_wrong_set_size_trusting_sound": (ValueError, "wrong set size"),
        "light_wrong_set_size_trusting_unsound": (ValueError, "wrong signature (#3)"),
        "light_power_short_trusting_sound": (NotEnoughVotingPowerError, "got 70, needed more than 80"),
        "light_power_short_trusting_unsound": (ValueError, "wrong signature (#3)"),
        "mixed_keys_fall_back_to_serial_in_each_check": None,
        "mixed_keys_bad_signature": (ValueError, "wrong signature (#"),
        "mixed_keys_in_the_light_set_only": None,
    }
    assert set(want) == set(CASES)
    for name, (build, _) in CASES.items():
        got = raised(walked_then_together, build())
        if want[name] is None:
            assert got is None, name
        else:
            assert got[0] is want[name][0] and want[name][1] in got[1], (name, got)
