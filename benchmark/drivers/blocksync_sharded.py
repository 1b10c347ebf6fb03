"""Closed loop, one joiner, one serving peer, over a chain whose commits
the engine splits across the process's chips: `benchmark/drivers/blocksync.py`'s
warm-up, window and checks, with its two refusal probes replaced by
four.

A commit's light batch (the rows VerifyCommitLight reads, 6667 at 10000
equal-power validators) goes over four chips in four shares. One
probe a quarter of that batch, its bad row drawn from the quarter's
upper half (rows 833-1665, 2500-3332, 4166-4999 and 5833-6666 of
6667): with each chip's share padded (1792 rows a chip there), each
range lies inside one chip's share and each chip holds one, so a route
that loses, reorders or misattributes a chip's verdicts is refused. Each time the joiner must stop below that
height, blame the peer with `wrong signature (#i)` for that row and not
halt, and the reference must refuse the same commit.

Parameters (`benchmark/traffic/<mix>.json`): `blocksync`'s.
"""

from __future__ import annotations

from benchmark import chain as chainlib
from benchmark import reference as ref
from benchmark.drivers import blocksync as base

QUARTERS = 4


def probe_rows(prefix: int) -> list[range]:
    """The rows the probes draw from: the upper half of each quarter of
    a light batch of `prefix` rows."""
    return [range((2 * q + 1) * prefix // (2 * QUARTERS), (q + 1) * prefix // QUARTERS)
            for q in range(QUARTERS)]


class Traffic(base.Traffic):
    def _refusal(self, rng) -> int:
        """Four probes, one in each quarter of the light batch. Returns
        the number of things that went wrong (`probe`)."""
        lo, hi = self.params["refusal_heights"]
        hi = min(hi, self.chain.height - 2)
        faults, self.refusal = 0, []
        for rows in probe_rows(chainlib.signing_prefix(self.chain, 2, 3)):
            record = self.probe(rng.randint(min(lo, hi), hi), rng.choice(rows))
            self.refusal.append(record)
            faults += record["faults"]
        return faults

    def probe(self, commit_height: int, bad_index: int) -> dict:
        """A fresh joiner whose only peer serves the chain with signature
        `bad_index` of the commit for `commit_height` broken."""
        chain = self.chain
        served = chainlib.corrupted_store(chain, commit_height, bad_index)
        sigs, msgs = chainlib.commit_values(
            chain, served.load_block(commit_height + 1).last_commit)
        accepted, _ = ref.commit_verdict(chain.pubkeys, chain.powers, sigs, msgs, 2, 3, True)
        p = base.Pass(chain, serve_from=served, stop_on_peer_error=True)
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
