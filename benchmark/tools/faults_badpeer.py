"""The controls for a joiner among lying peers, beside
`benchmark/tools/faults.py`'s faults: the two checks that stand between
a served lie and the block store, each left out.

    tail_unchecked   the reactor's validation before save_block (`blocksync/reactor.py`,
                     `block_exec.validate_block` on the block the commit proved) passes
                     whatever it is given; `apply_block`'s own validation, after the block
                     is saved, still runs. Alone it is seen by no number of the cell: a lie
                     in a block's bytes never gets that far (below)
    parts_unchecked  `types/validation.py` compares the commit's block ID with the one the
                     joiner computed by the header's hash alone, the part set's hash left
                     out: the check that refuses a block whose bytes are not the ones that
                     were signed. A lie beyond the light prefix then passes the commit and
                     is stopped by the validation before save_block, one stage late
    parts_and_tail_unchecked  both: the parent's program with the first check gone. The
                     block is saved, `apply_block` refuses it, the node halts with its block
                     store above its state

`python3 -m benchmark.tools.faults_badpeer` is `benchmark.tools.many`
with those faults beside the others:

    python3 -m benchmark.tools.faults_badpeer --workload blocksync-1k-badpeer --seconds 8 \\
        --seeds 11,21:tail_unchecked,31:parts_unchecked,41:parts_and_tail_unchecked
"""

from __future__ import annotations

import sys

from benchmark.tools import faults, many


def tail_unchecked():
    from tendermint_tpu.state import BlockExecutor

    validate_block = BlockExecutor.validate_block
    passed = set()  # blocks whose first validation, the reactor's, was let through

    def first_call_passes(self, state, block):
        if id(block) in passed:
            passed.discard(id(block))
            return validate_block(self, state, block)
        passed.add(id(block))

    return faults._patch(BlockExecutor, "validate_block", first_call_passes)


def parts_unchecked():
    from tendermint_tpu.types import validation

    check = validation._verify_basic_vals_and_commit

    def by_the_hash_alone(vals, commit, height, block_id):
        if commit is not None and block_id.hash == commit.block_id.hash:
            block_id = commit.block_id
        return check(vals, commit, height, block_id)

    return faults._patch(validation, "_verify_basic_vals_and_commit", by_the_hash_alone)


def parts_and_tail_unchecked():
    undo = [parts_unchecked(), tail_unchecked()]
    return lambda: [u() for u in undo]


def register() -> None:
    for fault in (tail_unchecked, parts_unchecked, parts_and_tail_unchecked):
        faults.FAULTS.setdefault(fault.__name__, fault)


def main(argv=None, **kwargs) -> int:
    register()
    return many.main(argv, **kwargs)


if __name__ == "__main__":
    sys.exit(main())
