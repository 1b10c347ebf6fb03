"""Closed loop, one joiner, one serving peer, over a chain whose
validator set changes every block: `benchmark/drivers/blocksync.py`'s
warm-up and window over `benchmark/chain_churn.py`'s chain, with two
differences. The joiner's application goes through the program's
`Handshaker` before its reactor starts, as a node's start-up does: it
gets InitChain with the genesis set, without which it refuses the
first `val:<leaver>!0` ("Cannot remove non-existent validator") and
its LastResultsHash parts from the chain's at height 2. And the
joiner's reactor counts its verify-ahead outcomes, which the window
hands to the readers with the pubkey cache's fill counters, both over
the whole window.

Parameters (`benchmark/traffic/<mix>.json`): `blocksync`'s.

`correct` holds the joiner to the rotation, against
`benchmark/reference_churn.py`'s schedule (written from the rotation
rule alone): each commit it applied is verified by the reference with
the pubkeys and powers of that height's set; its final three sets and
the set its state store gives at every sampled height are that
schedule's, by the reference's hash; and a forged commit at a rotated
height is refused.
"""

from __future__ import annotations

import contextlib
import hashlib
import random

from benchmark import chain as chainlib
from benchmark import chain_churn
from benchmark import reference as ref
from benchmark import reference_churn as refc
from benchmark.drivers import Check
from benchmark.drivers import blocksync as base

FILLS = {  # EngineMetrics attribute -> the window's key, summed over planes
    "pk_cache_fills": "pk_fills", "pk_cache_filled_keys": "pk_filled_keys",
    "pk_cache_fill_rows": "pk_fill_rows", "pk_cache_fill_seconds": "pk_fill_s",
}


def fill_counters() -> dict:
    """The engine's pubkey-cache fill counters under the window's keys;
    none on a program that has no such counters."""
    from tendermint_tpu.metrics import engine_metrics

    m = engine_metrics()
    return {key: sum(value for _, _, value in getattr(m, attr).samples())
            for attr, key in FILLS.items() if hasattr(m, attr)}


class Pass(base.Pass):
    """`blocksync.Pass` with the joiner's application handshaken and its
    verify-ahead outcomes counted."""

    def __init__(self, chain, serve_from=None, stop_on_peer_error=False):
        from tendermint_tpu.consensus.handshake import Handshaker
        from tendermint_tpu.metrics import BlockSyncMetrics, Registry

        super().__init__(chain, serve_from, stop_on_peer_error)
        reactor = self.reactor
        self.state_store = reactor.block_exec.store
        reactor.state = Handshaker(self.state_store, reactor.state, self.block_store,
                                   chain.gen_doc).handshake(reactor.block_exec.app)
        self.metrics = reactor.metrics = BlockSyncMetrics(Registry())

    def verify_ahead(self) -> dict:
        """{outcome: count} of the commits the joiner verified a height ahead."""
        return {labels["outcome"]: value
                for _, labels, value in self.metrics.verify_ahead.samples()}


@contextlib.contextmanager
def _joiners():
    """`blocksync.Traffic`'s loops make their joiners by their module's
    name `Pass`: for the length of one call, this module's."""
    was, base.Pass = base.Pass, Pass
    try:
        yield
    finally:
        base.Pass = was


class Traffic(base.Traffic):
    def build(self) -> None:
        config = self.config
        self.chain = chain_churn.build(config, self.seed)
        # the reference's side: keys from the seed, sets from the rotation rule
        per_block = config["rotation"]["validators_per_block"]
        keys = [ref.public_key(s) for s in chainlib.key_seeds(
            self.seed, config["validators"] + config["blocks"] * per_block)]
        self.sets = refc.Schedule(keys, config["validators"], config["voting_power"], per_block)
        self._verdicts: dict[tuple, bool] = {}

    def warm_up(self) -> None:
        """`blocksync`'s short sync, then the programs of a coalesced
        group. The reactor submits the next height's light proof, then
        validates the block in full; when the engine's dispatch thread is
        late it joins the two into one launch of light + full rows
        (1667 -> 2048 at 1000 validators), a bucket the short sync may
        never reach, and where the group carries a key the cache has not
        seen its fill runs at that bucket too: three programs that would
        otherwise load inside the window, 40-85 s each on a cold cache.
        One batch of that many rows under keys made for it, none of
        which the window looks up, loads all three here."""
        from tendermint_tpu.ops import verify as V

        with _joiners():
            super().warm_up()
        n = self.config["validators"]
        _, light = refc.light_rows(self.sets.set_at(1), [True] * n)
        seeds = [hashlib.sha256(b"bench-coalesced:%d:%d" % (self.seed, i)).digest()
                 for i in range(len(light) + n)]
        msgs = [b"coalesced group %d" % i for i in range(len(seeds))]
        ok = V.verify_batch_cached([ref.public_key(s) for s in seeds], msgs,
                                   [ref.signer(s)(m) for s, m in zip(seeds, msgs)])
        if not ok.all():
            raise RuntimeError(f"the coalesced group's warm-up refused {int((~ok).sum())} rows")

    def window(self, seconds: float) -> dict:
        before = fill_counters()
        with _joiners():
            out = super().window(seconds)
        after = fill_counters()
        out.update({key: after[key] - before[key] for key in after})
        for outcome in ("used", "stale"):
            out["verify_ahead_" + outcome] = sum(p.verify_ahead().get(outcome, 0.0)
                                                 for p in self.passes)
        return out

    # ------------------------------------------------------------- correct

    def set_differs(self, vals, height: int) -> bool:
        """A program ValidatorSet against the schedule's set at `height`,
        by the reference's hash over its (pubkey, power) in its order."""
        return vals is None or refc.validator_set_hash(
            [(v.pub_key.bytes(), v.voting_power) for v in vals.validators]
        ) != self.sets.hash_at(height)

    def accepted(self, height: int, commit, light: bool) -> bool:
        """`reference.commit_verdict` on a program Commit for `height`,
        with that height's pubkeys and powers from the schedule; the
        passes of a window store the same commits, so a verdict is
        computed once for the bytes it is about."""
        sigs, msgs = chainlib.commit_values(self.chain, commit)
        key = (height, light, tuple(sigs), tuple(msgs))
        if key not in self._verdicts:
            signing = self.sets.set_at(height)
            self._verdicts[key], _ = ref.commit_verdict(
                [pk for pk, _ in signing], [power for _, power in signing], sigs, msgs, 2, 3,
                light)
        return self._verdicts[key]

    def check(self) -> tuple[list[Check], int, int]:
        """(checks, attempted, failed): every block the window's joiners
        applied against the source, the reference and the schedule, then
        two commits that must be refused."""
        chain, rng = self.chain, random.Random(self.seed)
        wrong_hash = wrong_header = wrong_commit = wrong_app = wrong_sets = halted = 0
        applied = 0
        for p in self.passes:
            height = p.block_store.height()
            applied += height
            if p.fatal is not None or p.peer_errors:
                halted += 1
            for h in range(1, height + 1):
                meta = p.block_store.load_block_meta(h)
                wrong_hash += meta is None or meta.block_id.hash != chain.block_hashes[h - 1]
            if height == 0:
                continue
            state = p.reactor.state
            # the app hash counts the kvstore pairs only: a val: tx adds none
            wrong_app += (state.last_block_height != height
                          or state.app_hash != ref.kvstore_app_hash(chain.txs_per_block * height))
            wrong_sets += (self.set_differs(state.last_validators, height)
                           + self.set_differs(state.validators, height + 1)
                           + self.set_differs(state.next_validators, height + 2))
            sample = set(rng.sample(range(1, height + 1),
                                    min(self.params["check_sample"], height)))
            sample.add(height)
            for h in sorted(sample):
                block = p.block_store.load_block(h)
                wrong_header += (ref.header_hash(chainlib.header_values(block.header))
                                 != chain.block_hashes[h - 1])
                # the set the joiner stored for h, and the one the source's builder recorded
                wrong_sets += self.set_differs(p.state_store.load_validators(h), h)
                wrong_sets += refc.validator_set_hash(chain.sets[h - 1]) != self.sets.hash_at(h)
                # the commit blocksync proved h with (the light rule) ...
                wrong_commit += not self.accepted(h, p.block_store.load_seen_commit(h), True)
                # ... and the one apply_block validated in full
                if h > 1:
                    wrong_commit += not self.accepted(h - 1, block.last_commit, False)
        refusal = self._refusal(rng)
        checks = [
            Check("blocks_differing_from_source", wrong_hash, 0),
            Check("headers_differing_from_reference_hash", wrong_header, 0),
            Check("applied_commits_the_reference_refuses", wrong_commit, 0),
            Check("app_hash_or_height_wrong", wrong_app, 0),
            Check("validator_sets_differing_from_schedule", wrong_sets, 0),
            Check("passes_halted_or_blaming_an_honest_peer", halted, 0),
            Check("refusal_faults", refusal, 0),
        ]
        failed = sum(c.value for c in checks)
        return checks, applied + 2, failed

    def _refusal(self, rng) -> int:
        """Twice, a peer serves a commit with one signature the curve
        equation refuses, at a height whose set the rotation has moved:
        once in the first half of the rows VerifyCommitLight reads in
        that height's set, once in the second. Returns the number of
        things that went wrong (`probe`)."""
        lo, hi = self.params["refusal_heights"]
        hi = min(hi, self.chain.height - 2)
        n = len(self.sets.set_at(hi))
        faults, self.refusal = 0, []
        for half in (0, 1):
            commit_height = rng.randint(min(lo, hi), hi)
            _, rows = refc.light_rows(self.sets.set_at(commit_height), [True] * n)
            bad_index = rng.choice(rows[len(rows) // 2:] if half else rows[: len(rows) // 2])
            record = self.probe(commit_height, bad_index)
            self.refusal.append(record)
            faults += record["faults"]
        return faults

    def probe(self, commit_height: int, bad_index: int) -> dict:
        """A fresh joiner whose only peer serves the chain with signature
        `bad_index` of the commit for `commit_height` broken. The joiner
        must stop below that height, blame the peer with that row's
        verdict and not halt, and the reference must refuse the same
        commit with the schedule's set for that height."""
        served = chainlib.corrupted_store(self.chain, commit_height, bad_index)
        commit = served.load_block(commit_height + 1).last_commit
        accepted = self.accepted(commit_height, commit, True)
        p = Pass(self.chain, serve_from=served, stop_on_peer_error=True)
        p.start()
        p.done.wait(600.0)
        p.stop()
        faults = int(accepted) + (p.fatal is not None)
        faults += p.block_store.height() != commit_height - 1
        faults += not (p.peer_errors and isinstance(p.peer_errors[0].err, ValueError)
                       and f"wrong signature (#{bad_index})" in str(p.peer_errors[0].err))
        return {
            "commit_height": commit_height, "bad_index": bad_index,
            "reference_accepts": accepted, "joiner_height": p.block_store.height(),
            "fatal": repr(p.fatal) if p.fatal is not None else None,
            "peer_errors": [f"{type(e.err).__name__}: {str(e.err)[:40]}" for e in p.peer_errors],
            "faults": faults,
        }
