"""The control for a chain whose validator set rotates, beside
`benchmark/tools/faults.py`'s faults: the guarantee bisection exists to
keep, broken in the cheapest tempting way.

    trusting_passes  `light/verifier.py`'s trusting check (more than 1/3 of the
                     TRUSTED set's power signed the new commit) passes whatever it is
                     given, so the client never bisects: it stores any header that more
                     than 2/3 of its own set signed, which is what a client that follows
                     a forged validator set does

`python3 -m benchmark.tools.faults_churn` is `benchmark.tools.many` with
that fault beside the others:

    python3 -m benchmark.tools.faults_churn --workload light-150-churn --seconds 8 \\
        --seeds 11,21:trusting_passes,31:half_batch
"""

from __future__ import annotations

import sys

from benchmark.tools import faults, many


def trusting_passes():
    from tendermint_tpu.light import verifier

    return faults._patch(verifier, "verify_commit_light_trusting", lambda *args, **kwargs: None)


def main(argv=None, **kwargs) -> int:
    faults.FAULTS.setdefault(trusting_passes.__name__, trusting_passes)
    return many.main(argv, **kwargs)


if __name__ == "__main__":
    sys.exit(main())
