"""Several seeds of one cell in one process, each traced or not: how
the cost of tracing (operations in the window, traced against untraced)
and the stall hunt of PR 35 were taken on the chip, where a process's
program loads are most of a run's set-up. `many.py` runs untraced only.
Not the driver's measurement: `setup_s` of a later seed is the
process's age.

    python3 -m benchmark.tools.many_traced --workload light-150-skip --seconds 25 \\
        --seeds 2147000001,2147000002:off,2147000003

A seed is traced unless `:off` follows it. One line a seed: `correct`
and the metrics the run reports (end to end untraced, per layer
traced); the harness's `window:` line (operations in the window) and
the readers' own log lines (`slowest op:`, `refusal gap:`) precede it.
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark import run


def main(argv=None, require_tpu: bool = True, root: str = run.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated; seed:off runs it untraced")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    plan = [(int(seed), off != "off")
            for seed, _, off in (item.partition(":") for item in args.seeds.split(","))]
    try:
        # traced: the program is imported with the ring at a traced run's size
        harness = run.Harness(args.workload, True, require_tpu, root)
    except run.Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return e.code
    for seed, traced in plan:
        harness.traced = traced
        result, checks = harness.run(seed, args.seconds)
        print(json.dumps({"seed": seed, "traced": traced, "correct": result["correct"],
                          "failing": {c.name: c.value for c in checks if not c.ok},
                          "metrics": {name: m["value"] for name, m in result["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
