"""The control for a joiner of a chain whose validator set rotates,
beside `benchmark/tools/faults.py`'s faults: the H + 2 rule broken in
the cheapest tempting way.

    changes_at_once  `State.update` lets a block's validator updates act from the
                     next height, H + 1, instead of H + 2: the state's `validators`
                     after block H is the set its updates made. The joiner then
                     verifies the next commit against a set one rotation ahead of
                     the one that signed it, and holds a set the schedule does not

`python3 -m benchmark.tools.faults_sync_churn` is `benchmark.tools.many`
with that fault beside the others:

    python3 -m benchmark.tools.faults_sync_churn --workload blocksync-1k-churn --seconds 8 \\
        --seeds 11,21:changes_at_once,31:half_batch
"""

from __future__ import annotations

import sys

from benchmark.tools import faults, many


def changes_at_once():
    from tendermint_tpu.state.state import State

    update = State.update

    def at_once(self, block_id, header, results_hash, params, validator_updates):
        state = update(self, block_id, header, results_hash, params, validator_updates)
        if validator_updates:
            state.validators = state.next_validators.copy()
        return state

    return faults._patch(State, "update", at_once)


def main(argv=None, **kwargs) -> int:
    faults.FAULTS.setdefault(changes_at_once.__name__, changes_at_once)
    return many.main(argv, **kwargs)


if __name__ == "__main__":
    sys.exit(main())
