"""Several seeds of one cell in one process, with or without a fault:
how the limits' readings and the control's were taken on the chip
(PERF.md section 2), where a run's set-up is long. Not a measurement:
it prints `correct` and what was compared for each seed, no metric.

    python3 -m benchmark.tools.many --workload blocksync-1k --seconds 8 \\
        --seeds 11,12,13,21:lowered_verify,22:lowered_verify,31:half_batch
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark import run
from benchmark.tools import faults


def main(argv=None, require_tpu: bool = True, root: str = run.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated; seed:fault runs that seed with a fault of "
                         f"benchmark/tools/faults.py ({', '.join(sorted(faults.FAULTS))})")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    plan = [(int(seed), fault or None)
            for seed, _, fault in (item.partition(":") for item in args.seeds.split(","))]
    try:
        harness = run.Harness(args.workload, False, require_tpu, root)
    except run.Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return e.code
    for seed, fault in plan:
        undo = []
        try:
            result, checks = harness.run(
                seed, args.seconds,
                before_window=(lambda: undo.append(faults.FAULTS[fault]())) if fault else None)
        finally:
            for u in undo:
                u()
        print(json.dumps({"seed": seed, "fault": fault, "correct": result["correct"],
                          "failing": {c.name: c.value for c in checks if not c.ok}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
