"""Commit verification — the north-star path (ref: types/validation.go).

All four consumers (block application, blocksync, light client, evidence)
funnel here. Semantics preserved exactly from the reference:
  - batch path for >=2 signatures with a batch-capable key type (:12-16)
  - tally-before-verify with the voting-power check preceding the
    signature check (:237)
  - early-break once power exceeds the threshold when not counting all
    signatures (:225-233)
  - first-invalid-index reporting on batch failure (:245-255)
  - by-address lookup + double-vote detection for the trusting path
    (:190-210)

The batch verifier itself is the TPU plane (crypto/ed25519.py ->
ops/verify.py): one device launch evaluates every signature's cofactored
ZIP-215 equation data-parallel, so unlike the reference no serial
re-verification pass is needed to locate a bad signature.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

from .. import trace as _trace
from ..crypto import batch as crypto_batch
from .block import BlockID, Commit, CommitSig
from .validator_set import NotEnoughVotingPowerError, ValidatorSet

# ref: types/validation.go:12
BATCH_VERIFY_THRESHOLD = 2


@dataclass(frozen=True)
class Fraction:
    """ref: libs/math/fraction.go."""

    numerator: int
    denominator: int


def _should_batch_verify(vals: ValidatorSet, commit: Commit) -> bool:
    """ref: shouldBatchVerify (types/validation.go:14)."""
    if len(commit.signatures) < BATCH_VERIFY_THRESHOLD:
        return False
    proposer = vals.get_proposer()
    return proposer is not None and crypto_batch.supports_batch_verifier(proposer.pub_key)


def verify_commit(chain_id: str, vals: ValidatorSet, block_id: BlockID, height: int, commit: Commit) -> None:
    """Verify +2/3 signed AND check every signature (ref: VerifyCommit,
    types/validation.go:27 — all signatures are checked because apps'
    incentivization logic depends on LastCommitInfo)."""
    verify_commit_async(chain_id, vals, block_id, height, commit)()


def verify_commit_async(
    chain_id: str, vals: ValidatorSet, block_id: BlockID, height: int, commit: Commit
):
    """verify_commit split at the device boundary, mirroring
    verify_commit_light_async: host-side checks raise NOW, the
    signature batch is dispatched (through the coalescing engine when
    enabled — concurrent dispatches from blocksync, the light client,
    and evidence verification merge into one launch), and the returned
    no-arg callable raises (or not) with verify_commit's exact error
    surface. Lets a caller overlap two verifications — e.g. blocksync
    checks an extended commit's vote signatures and its extension
    signatures in flight together instead of back to back."""
    _verify_basic_vals_and_commit(vals, commit, height, block_id)
    voting_power_needed = vals.total_voting_power() * 2 // 3
    ignore = lambda c: c.block_id_flag == 1  # absent
    count = lambda c: c.block_id_flag == 2  # commit
    return _submit_walked(
        _walk_commit(chain_id, vals, commit, voting_power_needed, ignore, count, True, True))


def verify_commit_light(chain_id: str, vals: ValidatorSet, block_id: BlockID, height: int, commit: Commit) -> None:
    """Verify +2/3 signed, early-exit once reached (ref: VerifyCommitLight,
    types/validation.go:61). One body with the async variant — the
    blocksync verify-ahead guards rely on the two being semantically
    identical."""
    verify_commit_light_async(chain_id, vals, block_id, height, commit)()


def verify_commit_light_async(
    chain_id: str, vals: ValidatorSet, block_id: BlockID, height: int, commit: Commit
):
    """verify_commit_light split at the device boundary: all host-side
    checks (structure, tally, power threshold) run NOW and raise
    immediately; the signature kernel is dispatched and the returned
    no-arg callable raises (or not) with verify_commit_light's exact
    error surface when invoked. Lets blocksync verify height h+1 on the
    chip while height h applies host-side (the verify-ahead pipeline —
    a capability the reference's serial verify loop lacks)."""
    return _submit_walked(_walk_commit_light(chain_id, vals, block_id, height, commit))


def _walk_commit_light(
    chain_id: str, vals: ValidatorSet, block_id: BlockID, height: int, commit: Commit
) -> _WalkedBatch | None:
    """verify_commit_light's host half: every check that needs no
    signature verified raises NOW (see _walk_commit for the result)."""
    _verify_basic_vals_and_commit(vals, commit, height, block_id)
    voting_power_needed = vals.total_voting_power() * 2 // 3
    ignore = lambda c: c.block_id_flag != 2
    count = lambda c: True
    return _walk_commit(chain_id, vals, commit, voting_power_needed, ignore, count, False, True)


def verify_commit_light_trusting(
    chain_id: str, vals: ValidatorSet, commit: Commit, trust_level: Fraction, hold: bool = False
) -> _WalkedBatch | None:
    """Verify trustLevel of an arbitrary validator set signed, looking
    validators up by address (ref: VerifyCommitLightTrusting,
    types/validation.go:96).

    With hold=True only the host's half runs: arguments, address
    lookup, double votes and the power tally raise NOW, a power
    shortfall (NotEnoughVotingPowerError: the one failure a light
    client answers by bisecting) among them, and the batch comes back
    walked and not submitted, for verify_commit_light_after_trusting
    with the same commit; None where the commit went the serial way and
    is already verified."""
    if vals is None:
        raise ValueError("nil validator set")
    if trust_level.denominator == 0:
        raise ValueError("trustLevel has zero Denominator")
    if commit is None:
        raise ValueError("nil commit")
    product = vals.total_voting_power() * trust_level.numerator
    if product >= 2**63:
        raise OverflowError("int64 overflow while calculating voting power needed")
    voting_power_needed = product // trust_level.denominator
    ignore = lambda c: c.block_id_flag != 2
    count = lambda c: True
    walked = _walk_commit(chain_id, vals, commit, voting_power_needed, ignore, count, False, False)
    if hold:
        return walked
    _submit_walked(walked)()


def verify_commit_light_after_trusting(
    trusting: _WalkedBatch | None,
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
) -> None:
    """The two checks of a light client's non-adjacent step as one
    engine submission (ref: light/verifier.go:70-95). `trusting` is what
    verify_commit_light_trusting(hold=True) returned for this commit
    against the trusted set; this walks verify_commit_light against the
    commit's own set, hands both batches to the engine in one call (one
    launch where two blocking calls make two) and returns when both
    verdicts are in. It raises what verify_commit_light_trusting's wait
    and then verify_commit_light would raise, in that order: where the
    light walk fails on the host the trusting batch is still verified,
    alone, and its refusal comes first; where both batches hold a bad
    signature the one reported is the trusting check's. No verdict is
    shared: rows that both batches hold are verified in both."""
    try:
        light = _walk_commit_light(chain_id, vals, block_id, height, commit)
    except Exception:
        _submit_walked(trusting)()
        raise
    batches = [b for b in (trusting, light) if b is not None]
    _dispatch_walked(batches)
    for batch in batches:
        batch.collect()


class _WalkedBatch:
    """A commit's signatures walked into a BatchVerifier, its power
    tallied and found enough: all of a check that the host decides.
    Held until _dispatch_walked submits it, alone or beside another;
    collect() then waits for its verdicts."""

    __slots__ = ("commit", "bv", "sig_idxs", "pending")

    def __init__(self, commit: Commit, bv, sig_idxs: list[int]):
        self.commit = commit
        self.bv = bv
        self.sig_idxs = sig_idxs  # row i of the batch is commit.signatures[sig_idxs[i]]
        self.pending = None

    def collect(self) -> None:
        """ref: types/validation.go:245-255, without the serial pass."""
        with _trace.span("verify.commit_collect", "verify",
                         height=self.commit.height, nsigs=len(self.sig_idxs)):
            ok, valid_sigs = self.pending()
        if ok:
            return
        for i, sig_ok in enumerate(valid_sigs):
            if not sig_ok:
                idx = self.sig_idxs[i]
                sig = self.commit.signatures[idx].signature
                raise ValueError(f"wrong signature (#{idx}): {sig.hex().upper()}")
        raise RuntimeError("BUG: batch verification failed with no invalid signatures")


def _dispatch_walked(batches: list[_WalkedBatch]) -> None:
    """Submit walked batches of one commit in one call, under one
    verify.commit_dispatch span (`jobs` where there are several)."""
    if not batches:
        return
    args = {"height": batches[0].commit.height, "nsigs": sum(len(b.sig_idxs) for b in batches)}
    if len(batches) > 1:
        args["jobs"] = len(batches)
    with _trace.span("verify.commit_dispatch", "verify", **args):
        pendings = crypto_batch.verify_async_together([b.bv for b in batches])
    for batch, pending in zip(batches, pendings):
        batch.pending = pending


def _submit_walked(walked: _WalkedBatch | None):
    """One walked batch on its way, and the no-arg callable that waits
    for it and raises (or not) with the blocking check's errors; a
    commit verified serially (None) has nothing left to wait for."""
    if walked is None:
        return lambda: None
    _dispatch_walked([walked])
    return walked.collect


def _walk_commit(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable[[CommitSig], bool],
    count_sig: Callable[[CommitSig], bool],
    count_all_signatures: bool,
    look_up_by_index: bool,
) -> _WalkedBatch | None:
    """The host's half of a check, by the way the commit goes (ref:
    types/validation.go:12-16): a batch walked, tallied and still to be
    submitted, or None where the commit was verified serially, here and
    now, and nothing is left to wait for."""
    args = (chain_id, vals, commit, voting_power_needed, ignore_sig, count_sig,
            count_all_signatures, look_up_by_index)
    if _should_batch_verify(vals, commit):
        return _walk_commit_batch(*args)
    _verify_commit_single(*args)
    return None


def _walk_commit_batch(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable[[CommitSig], bool],
    count_sig: Callable[[CommitSig], bool],
    count_all_signatures: bool,
    look_up_by_index: bool,
) -> _WalkedBatch | None:
    """ref: verifyCommitBatch (types/validation.go:154), up to the line
    where the batch is verified: host-side failures raise here and the
    batch comes back unsubmitted. None where a key could not join the
    batch and the commit was verified serially instead."""
    with _trace.span("verify.commit_walk", "verify", height=commit.height) as walk:
        proposer = vals.get_proposer()
        bv = crypto_batch.create_batch_verifier(proposer.pub_key)
        if _trace.enabled():
            # tmpath journey tag: rides the engine submit so the coalesced
            # launch's dispatch/collect spans list this commit's height —
            # the height attribution lens/journey.py splits verify time by
            bv.journey = _trace.journey_key(commit.height, commit.round, "verify", "")
        tallied = 0
        seen_vals: dict[int, int] = {}
        batch_sig_idxs: list[int] = []

        for idx, commit_sig in enumerate(commit.signatures):
            if ignore_sig(commit_sig):
                continue
            if look_up_by_index:
                val = vals.validators[idx]
            else:
                val_idx, val = vals.get_by_address(commit_sig.validator_address)
                if val is None:
                    continue
                if val_idx in seen_vals:
                    raise ValueError(f"double vote from {val} ({seen_vals[val_idx]} and {idx})")
                seen_vals[val_idx] = idx
            vote_sign_bytes = commit.vote_sign_bytes(chain_id, idx)
            try:
                bv.add(val.pub_key, vote_sign_bytes, commit_sig.signature)
            except ValueError:
                # Mixed key types: this key cannot join the proposer-typed
                # batch. The reference returns the Add error outright
                # (validation.go:211), rejecting commits that are in fact
                # valid; we deliberately fall back to serial verification
                # instead — acceptance still requires every signature to
                # verify, so no invalid commit is admitted.
                walk.annotate(fallback="single")
                _verify_commit_single(
                    chain_id, vals, commit, voting_power_needed,
                    ignore_sig, count_sig, count_all_signatures, look_up_by_index,
                )
                return None
            batch_sig_idxs.append(idx)
            if count_sig(commit_sig):
                tallied += val.voting_power
            if not count_all_signatures and tallied > voting_power_needed:
                break

        # the batch path has two signatures at least: idx is the last one walked
        walk.annotate(nsigs=len(batch_sig_idxs), walked=idx + 1)
        if tallied <= voting_power_needed:
            raise NotEnoughVotingPowerError(got=tallied, needed=voting_power_needed)
    return _WalkedBatch(commit, bv, batch_sig_idxs)


def _verify_commit_single(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable[[CommitSig], bool],
    count_sig: Callable[[CommitSig], bool],
    count_all_signatures: bool,
    look_up_by_index: bool,
) -> None:
    """ref: verifyCommitSingle (types/validation.go:267)."""
    tallied = 0
    seen_vals: dict[int, int] = {}
    for idx, commit_sig in enumerate(commit.signatures):
        if ignore_sig(commit_sig):
            continue
        if look_up_by_index:
            val = vals.validators[idx]
        else:
            val_idx, val = vals.get_by_address(commit_sig.validator_address)
            if val is None:
                continue
            if val_idx in seen_vals:
                raise ValueError(f"double vote from {val} ({seen_vals[val_idx]} and {idx})")
            seen_vals[val_idx] = idx
        vote_sign_bytes = commit.vote_sign_bytes(chain_id, idx)
        if not val.pub_key.verify_signature(vote_sign_bytes, commit_sig.signature):
            raise ValueError(f"wrong signature (#{idx}): {commit_sig.signature.hex().upper()}")
        if count_sig(commit_sig):
            tallied += val.voting_power
        if not count_all_signatures and tallied > voting_power_needed:
            return
    if tallied <= voting_power_needed:
        raise NotEnoughVotingPowerError(got=tallied, needed=voting_power_needed)


def _verify_basic_vals_and_commit(vals: ValidatorSet, commit: Commit, height: int, block_id: BlockID) -> None:
    """ref: verifyBasicValsAndCommit (types/validation.go:328)."""
    if vals is None:
        raise ValueError("nil validator set")
    if commit is None:
        raise ValueError("nil commit")
    if vals.size() != len(commit.signatures):
        raise ValueError(f"invalid commit -- wrong set size: {vals.size()} vs {len(commit.signatures)}")
    if height != commit.height:
        raise ValueError(f"invalid commit -- wrong height: {height} vs {commit.height}")
    if block_id != commit.block_id:
        raise ValueError(f"invalid commit -- wrong block ID: want {block_id}, got {commit.block_id}")
