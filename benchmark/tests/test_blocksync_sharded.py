"""`blocksync-10k-4chip` at the rehearsal size (45 validators, 24 blocks;
its own `chain-tiny-10k`, written into the rehearsal's root as a new
cell's files are) on four virtual CPU devices, the engine's sharded route
forced at that size; the probes' rows against the chips' shares; the
three readers it brought, on hand-made slices; and its upper control.
Control flow and arithmetic only."""

import json
import os
import shutil

import pytest

# four virtual devices for the mesh, where no test of the session has
# started the backend yet (the tier-1 conftest's eight do as well)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=4").strip()

from benchmark.drivers.blocksync_sharded import probe_rows  # noqa: E402
from benchmark.metrics import (  # noqa: E402
    shard_pad_share,
    sharded_kernel_ms_per_launch,
    sharded_roofline,
)
from benchmark.tools import faults_sharded  # noqa: E402
from conftest import DATA, run_cell  # noqa: E402

CELL = "blocksync-10k-4chip"
CHIPS = 4


def tiny_config() -> dict:
    with open(os.path.join(DATA, "benchmark", "configs", "chain-tiny-10k.json")) as f:
        return json.load(f)


@pytest.fixture
def split_over_four(monkeypatch):
    """The engine's settings at the rehearsal's size: the 31-row light
    batch and the 45-row full one both split over four CPU devices (a
    chip's share of 4 rows is enough here; 512 on the chip), never
    coalesced, as 6667 + 10000 rows are not under MAX_COALESCE_ROWS, and
    a mesh cache of 64 slots (16384 on the chip), made afresh."""
    import jax

    import tendermint_tpu.crypto.ed25519 as ed
    from tendermint_tpu.ops import engine as E
    from tendermint_tpu.parallel import sharded_verify as S

    if len(jax.devices()) < CHIPS:
        pytest.skip(f"the backend started with {len(jax.devices())} devices")
    for key, value in tiny_config()["env"].items():
        monkeypatch.setenv(key, str(value))
    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", 6)
    monkeypatch.setattr(E, "SHARD_MIN_ROWS", 4)
    monkeypatch.setattr(E, "MAX_COALESCE_ROWS", 45)
    monkeypatch.setattr(S, "CACHE_SLOTS", 64)
    monkeypatch.setattr(S, "_CACHES", {})
    engine = E.get_engine()
    monkeypatch.setattr(engine, "_mesh", S.make_mesh(CHIPS))
    monkeypatch.setattr(engine, "_mesh_found", True)


@pytest.fixture
def sharded_root(tiny_root):
    name = "chain-tiny-10k.json"
    shutil.copy(os.path.join(DATA, "benchmark", "configs", name),
                os.path.join(tiny_root, "benchmark", "configs", name))
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "chain-tiny-10k", "source": "a rehearsal",
                             "file": "benchmark/configs/" + name,
                             "reduced": ["blocks", "validators"], "why": "rehearsal"})
    next(w for w in bench["workloads"] if w["name"] == CELL)["config"] = "chain-tiny-10k"
    with open(path, "w") as f:
        json.dump(bench, f)
    return tiny_root


def launches_by_path() -> dict:
    from tendermint_tpu.metrics import engine_metrics

    out: dict = {}
    for _, labels, value in engine_metrics().launches.samples():
        out[labels["path"]] = out.get(labels["path"], 0.0) + value
    return out


@pytest.mark.parametrize("prefix", [6667, 31])
def test_each_chips_share_holds_one_probes_rows(prefix):
    from tendermint_tpu.parallel.sharded_verify import chip_rows

    per = chip_rows(prefix, CHIPS)
    assert [{row // per for row in rows} for rows in probe_rows(prefix)] == [{0}, {1}, {2}, {3}]


def test_the_cell_is_correct_and_every_device_row_is_split(sharded_root, split_over_four, capsys):
    before = launches_by_path()
    code, result = run_cell(sharded_root, CELL, capsys=capsys)
    assert code == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"sync_rate", "setup_s"}
    assert {name: c["value"] for name, c in result["compared"].items()} == dict.fromkeys(
        ["blocks_differing_from_source", "headers_differing_from_reference_hash",
         "applied_commits_the_reference_refuses", "app_hash_or_height_wrong",
         "passes_halted_or_blaming_an_honest_peer", "refusal_faults",
         "programs_compiled_in_the_window", "windows_with_no_operation"], 0)
    grown = {p: v - before.get(p, 0.0) for p, v in launches_by_path().items()
             if v != before.get(p, 0.0)}
    assert set(grown) == {"sharded"}


def test_a_traced_run_reports_the_pad_share(sharded_root, split_over_four, capsys):
    """XLA:CPU has no device plane, so of the three new metrics only the
    padding's is read here; the two device readers are pinned below."""
    code, result = run_cell(sharded_root, CELL, seconds=3.0, trace=1, capsys=capsys)
    assert code == 0 and result["correct"] is True
    values = {name: m["value"] for name, m in result["metrics"].items()}
    # 31 -> 32 rows (8 a chip) and 45 -> 64 (16 a chip)
    assert 100 * 1 / 32 <= values["shard_pad_share.sync"] <= 100 * 19 / 64
    assert not {"sharded_kernel_ms_per_launch.sync", "sharded_roofline.sync"} & set(values)
    assert {"prep_ms_per_launch.sync", "apply_ms_per_block.sync", "compiles_in_window",
            "valset_rows_kept_share.sync", "slowest_op_offcpu_share.sync",
            "engine_device_cutover.sync"} <= set(values)


def test_correct_comes_out_false_with_a_chips_verdicts_dropped(sharded_root, split_over_four, capsys):
    undo = []
    try:
        code, result = run_cell(sharded_root, CELL, capsys=capsys,
                                before_window=lambda: undo.append(faults_sharded.shard_dropped()))
    finally:
        for u in undo:
            u()
    assert code == 0 and result["correct"] is False
    assert result["compared"]["refusal_faults"]["value"] >= 1


# -------------------------------------------------------------- readers


def slice_ctx(launches=2.0, rows=16667.0, ops=(("jit_sharded_verify", 0.020),),
              spans=((6667, 7168), (10000, 10240)), device=True) -> dict:
    key = lambda path: ("tendermint_engine_launches_total", (("path", path), ("plane", "ed25519")))
    rows_key = ("tendermint_engine_path_rows_total",
                (("path", "sharded"), ("plane", "ed25519"), ("status", "accept")))
    return {
        "counters": {"before": {key("sharded"): 10.0, rows_key: 100.0},
                     "after": {key("sharded"): 10.0 + launches, rows_key: 100.0 + rows,
                               key("host"): 3.0}},
        "device": {"ops": [list(op) for op in ops], "kernel_s": 0.03} if device else None,
        "spans": [{"name": "ops.verify_dispatch", "ends_in_slice": True, "t0": 0, "t1": 1,
                   "args": {"kernel": "sharded", "shards": CHIPS, "rows": r, "padded": p}}
                  for r, p in spans]
        + [{"name": "ops.verify_dispatch", "ends_in_slice": True, "t0": 0, "t1": 1,
            "args": {"kernel": "bitmap", "rows": 1000, "padded": 1024}}],
        "work": {"multiply_adds_per_verification": 309024},
        "peaks": {"int8_ops_per_s": 393e12},
    }


def test_the_sharded_kernel_time_is_a_chips_mean_per_launch():
    assert sharded_kernel_ms_per_launch.read(slice_ctx()) == pytest.approx(10.0)
    assert sharded_kernel_ms_per_launch.read(slice_ctx(launches=0.0)) is None
    assert sharded_kernel_ms_per_launch.read(slice_ctx(ops=(("jit_verify_kernel", 0.02),))) is None
    assert sharded_kernel_ms_per_launch.read(slice_ctx(device=False)) is None


def test_the_sharded_roofline_counts_every_chip():
    want = 100.0 * 16667 * 309024 * 2 / (0.020 * CHIPS * 393e12)
    assert sharded_roofline.read(slice_ctx()) == pytest.approx(want)
    assert 0 < want < 100
    assert sharded_roofline.read(slice_ctx(launches=0.0)) is None
    assert sharded_roofline.read(slice_ctx(spans=())) is None


def test_the_pad_share_reads_the_sharded_spans_alone():
    assert shard_pad_share.read(slice_ctx()) == pytest.approx(
        100.0 * (7168 - 6667 + 10240 - 10000) / (7168 + 10240))
    assert shard_pad_share.read(slice_ctx(spans=())) is None
