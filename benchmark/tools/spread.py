"""The spread of each end-to-end metric over sets of runs, as the bounds
were set from it (PERF.md section 2): the distance between the first and
third quartile (`statistics.quantiles(values, n=4)`) as a share of the
median, for each set, and the second set's median against the first's.

    python3 -m benchmark.tools.spread SET1_DIR SET2_DIR ...

Each directory holds one file per run whose last line starting with `{`
is the run's result line.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys


def result_line(path: str) -> dict | None:
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith('{"correct"')]
    return json.loads(lines[-1]) if lines else None


def main(dirs: list[str]) -> int:
    medians: dict[str, list[float]] = {}
    for d in dirs:
        runs = [r for r in (result_line(p) for p in sorted(glob.glob(os.path.join(d, "*.out"))))
                if r is not None]
        print(f"{d}: {len(runs)} runs, correct in {sum(r['correct'] for r in runs)}")
        for name in sorted({m for r in runs for m in r["metrics"]}):
            values = [r["metrics"][name]["value"] for r in runs if name in r["metrics"]]
            if len(values) < 2:
                continue
            q1, _, q3 = statistics.quantiles(values, n=4)
            med = statistics.median(values)
            medians.setdefault(name, []).append(med)
            print(f"  {name}: median {med:.6g}  spread {(q3 - q1) / med:.4%}  "
                  f"min {min(values):.6g} max {max(values):.6g}  "
                  + " ".join(f"{v:.5g}" for v in values))
    for name, meds in medians.items():
        if len(meds) == 2:
            print(f"{name}: second median against the first {meds[1] / meds[0] - 1:+.4%}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
