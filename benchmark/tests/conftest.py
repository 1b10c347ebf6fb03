"""Rehearsals of the benchmark on XLA:CPU at a tiny size: control flow
and arithmetic only, never a number of the device. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

They are not tier-1 (`tests/`), and the benchmark's runs do not run them.
"""

import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def make_root(tmp_path) -> str:
    """A copy of the benchmark's data files in which every cell runs
    the rehearsal configuration `chain-tiny` (device routes forced on
    XLA:CPU, cutovers scaled to its size)."""
    root = str(tmp_path / "root")
    for sub in ("configs", "traffic"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub), os.path.join(root, "benchmark", sub))
    for name in ("peaks.json", "work.json"):
        shutil.copy(os.path.join(ROOT, "benchmark", name), os.path.join(root, "benchmark", name))
    with open(os.path.join(DATA, "benchmark", "configs", "chain-tiny.json")) as f:
        tiny = json.load(f)
    with open(os.path.join(root, "benchmark", "configs", "chain-tiny.json"), "w") as f:
        json.dump(tiny, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "chain-tiny", "source": tiny["source"],
                             "file": "benchmark/configs/chain-tiny.json",
                             "reduced": tiny["reduced"], "why": "rehearsal"})
    for cell in bench["workloads"]:
        cell["config"] = "chain-tiny"
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)


def run_cell(root, workload, seed=5, seconds=2.0, trace=0, before_window=None, capsys=None):
    """The harness at the rehearsal size, the look for a chip skipped.
    Returns (exit code, the result line's object or None)."""
    from benchmark import run

    code = run.main(["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
                     "--trace", str(trace)], require_tpu=False, root=root,
                    before_window=before_window)
    out = capsys.readouterr().out.strip().splitlines() if capsys else []
    return code, (json.loads(out[-1]) if code == 0 and out else None)
