"""The reference's word on a lying peer's store: which heights lie, in
which row, and which pair of heights a joiner must refuse for each.
Written from the configuration and the seed alone; imports nothing of
the program. The verdicts themselves stay `reference.commit_verdict`
(the light rule and the full one).

The liar serves the honest chain with, at one height in two of
3 .. blocks - 2, one signature of that block's LastCommit whose s has
its lowest bit flipped. A joiner proves block h by the LastCommit block
h + 1 carries, read up to the row where more than 2/3 of the power has
signed (VerifyCommitLight), against the BlockID it computes itself from
the bytes it was served: the header's hash and the hash of the part set
(reactor.go poolRoutine: `first.MakePartSet`, `firstID`). So:

    row inside that prefix   the pair (h - 1, h) is refused: the signature fails
                             ("signature")
    row beyond it            the pair (h - 1, h) passes, the light rule never reads
                             the row; the pair (h, h + 1) is refused: block h's bytes
                             are not the ones block h + 1's LastCommit signed, its part
                             set hashes otherwise ("block_id")

Both are refused where the commit is checked, before ValidateBlock is
reached: no peer that lacks 2/3 of the keys can serve a block that
passes the commit and fails validation. Either way both senders of the
pair are blamed, and nothing of it is persisted.
"""

from __future__ import annotations

import random
from dataclasses import dataclass

SIGNATURE, BLOCK_ID = "signature", "block_id"


@dataclass(frozen=True)
class Lie:
    height: int  # the block whose LastCommit carries the flipped signature
    row: int  # in validator-set order
    kind: str  # SIGNATURE | BLOCK_ID: what refuses it
    pair: int  # the lower height of the pair that must be refused


def light_prefix(powers: list[int], num: int = 2, den: int = 3) -> int:
    """Rows VerifyCommitLight reads when every validator signed: up to
    the one at which more than num/den of the power is tallied."""
    needed, tallied = sum(powers) * num // den, 0
    for i, power in enumerate(powers):
        tallied += power
        if tallied > needed:
            return i + 1
    raise ValueError("the whole set does not hold that share")


def schedule(config: dict, seed: int) -> list[Lie]:
    """The liar's lies in height order."""
    powers = [config["voting_power"]] * config["validators"]
    prefix = light_prefix(powers)
    rng = random.Random(f"badpeer:{seed}")
    candidates = range(3, config["blocks"] - 1)
    lies = []
    for height in sorted(rng.sample(candidates, len(candidates) // 2)):
        row = rng.randrange(len(powers))
        lies.append(Lie(height, row, SIGNATURE, height - 1) if row < prefix
                    else Lie(height, row, BLOCK_ID, height))
    return lies


def refusable(lies: list[Lie]) -> dict[int, set[str]]:
    """Lower height of a pair -> the kinds of lie a joiner may refuse
    that pair for. A pair that is not a key holds no lie: whoever is
    blamed for it is blamed outside the rule."""
    out: dict[int, set[str]] = {}
    for lie in lies:
        out.setdefault(lie.pair, set()).add(lie.kind)
    return out
