"""metricsgen/metricsdiff analog (ref: scripts/metricsgen/metricsgen.go,
scripts/metricsgen/metricsdiff/).

The reference generates metrics *constructors* from annotated structs;
in Python the constructors are hand-written, so the generation runs in
the OTHER direction: this tool instantiates every metrics group against
a live Registry and emits the canonical metrics documentation
(docs/metrics.md) — name, type, labels, help — straight from the code,
so the docs can never drift from the registry. `--diff old.md` prints
added/removed/changed metrics between a previous doc and the current
code (the metricsdiff workflow for release notes).

Usage:
  python scripts/metricsgen.py               # print markdown to stdout
  python scripts/metricsgen.py --write       # rewrite docs/metrics.md
  python scripts/metricsgen.py --diff docs/metrics.md   # vs current code
  python scripts/metricsgen.py --check       # exit 1 if docs/metrics.md
                                             # drifted from the registry
                                             # (tier-1 CI gate)
"""

from __future__ import annotations

import os
import re
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

from tendermint_tpu import metrics as M  # noqa: E402

GROUPS = (
    "ConsensusMetrics",
    "MempoolMetrics",
    "P2PMetrics",
    "BlockSyncMetrics",
    "StateSyncMetrics",
    "EvidenceMetrics",
    "StateMetrics",
    "FaultNetMetrics",
    "EngineMetrics",
    "HashMetrics",
    "ProofMetrics",
    "LightMetrics",
    "FlightMetrics",
    "DeviceMetrics",
)


def collect():
    """[(group, name, type, labels, help)] from a live registry."""
    rows = []
    for group in GROUPS:
        reg = M.Registry()
        getattr(M, group)(reg)
        for metric in reg._metrics:
            kind = type(metric).__name__
            # '|' would corrupt both the markdown table and the --diff
            # parser; fail loudly at generation instead of drifting
            for field in (metric.name, metric.help, *metric.label_names):
                if "|" in field:
                    raise ValueError(
                        f"metric field contains '|' (breaks the table format): {field!r}"
                    )
            rows.append((group, metric.name, kind, tuple(metric.label_names), metric.help))
    return rows


def to_markdown(rows) -> str:
    out = [
        "# Metrics",
        "",
        "Generated by `scripts/metricsgen.py` from the live registry —",
        "do not edit by hand (ref: the reference's metricsgen-annotated",
        "`metrics.go` files; docs/core/metrics.md).",
        "",
    ]
    current = None
    for group, name, kind, labels, help_ in rows:
        if group != current:
            out.append(f"## {group}")
            out.append("")
            out.append("| Name | Type | Labels | Description |")
            out.append("|---|---|---|---|")
            current = group
        lbl = ", ".join(labels) if labels else "—"
        out.append(f"| `{name}` | {kind} | {lbl} | {help_} |")
    # blank line between tables
    text = []
    for line in out:
        if line.startswith("## ") and text and text[-1] != "":
            text.append("")
        text.append(line)
    return "\n".join(text) + "\n"


def parse_markdown(text: str):
    rows = set()
    group = None
    for line in text.splitlines():
        m = re.match(r"^## (\w+)", line)
        if m:
            group = m.group(1)
            continue
        m = re.match(r"^\| `([^`]+)` \| (\w+) \| ([^|]*) \| (.*) \|$", line)
        if m and group:
            labels = tuple(
                s.strip() for s in m.group(3).split(",") if s.strip() and s.strip() != "—"
            )
            rows.add((group, m.group(1), m.group(2), labels, m.group(4).strip()))
    return rows


def diff(old_text: str, rows) -> str:
    old = parse_markdown(old_text)
    new = {(g, n, k, l, h) for g, n, k, l, h in rows}
    old_names = {r[1]: r for r in old}
    new_names = {r[1]: r for r in new}
    lines = []
    for name in sorted(new_names.keys() - old_names.keys()):
        lines.append(f"+ {name}")
    for name in sorted(old_names.keys() - new_names.keys()):
        lines.append(f"- {name}")
    for name in sorted(new_names.keys() & old_names.keys()):
        if new_names[name] != old_names[name]:
            lines.append(f"~ {name}")
    return "\n".join(lines)


def main(argv) -> int:
    rows = collect()
    md = to_markdown(rows)
    if "--write" in argv:
        path = os.path.join(_ROOT, "docs", "metrics.md")
        with open(path, "w") as f:
            f.write(md)
        print(f"wrote {path} ({len(rows)} metrics)")
        return 0
    if "--check" in argv:
        # byte-exact drift gate: --diff only compares parsed rows, so
        # it misses formatting/ordering drift and hand edits to prose;
        # CI wants "docs/metrics.md IS what --write would produce"
        path = os.path.join(_ROOT, "docs", "metrics.md")
        try:
            with open(path) as f:
                on_disk = f.read()
        except OSError as e:
            print(f"cannot read {path}: {e}")
            return 1
        if on_disk == md:
            print(f"docs/metrics.md in sync ({len(rows)} metrics)")
            return 0
        delta = diff(on_disk, rows)
        print("docs/metrics.md drifted from the live registry — run "
              "`python scripts/metricsgen.py --write`")
        print(delta or "(row set identical; formatting/prose drift)")
        return 1
    if "--diff" in argv:
        path = argv[argv.index("--diff") + 1]
        with open(path) as f:
            delta = diff(f.read(), rows)
        print(delta or "no metric changes")
        return 0 if not delta else 1
    sys.stdout.write(md)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
