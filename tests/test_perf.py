"""tmperf — the performance-regression observatory
(tendermint_tpu/perf/, scripts/tmperf.py, docs/observability.md#tmperf).

Tier-1, device-free. The compare-math cases are the ISSUE-12
acceptance set: identical re-runs must NOT trip (no noise false
positive), an injected 30% slowdown MUST trip naming the stage and
the measured delta, small samples refuse to gate, cross-fingerprint
deltas demote to informational, torn ledger tails are tolerated, and
the CLI honors the tmlens rc contract (0/1/2).
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "scripts"))

from tendermint_tpu.perf import (  # noqa: E402
    Samples,
    append_records,
    bless,
    compare_run,
    compare_to_baseline,
    coverage_gaps,
    fingerprint,
    fp_id,
    latest_run,
    load_baselines,
    make_record,
    median_mad,
    rate_samples,
    read_ledger,
    record_key,
    run_groups,
    save_baselines,
)
from tendermint_tpu.perf.record import validate_record  # noqa: E402

FP = fingerprint(device="cpu")
OTHER_FP = dict(FP, device="tpu:TPU v4")
OTHER_FP["fp"] = fp_id(OTHER_FP)


def rec(
    median=100.0, mad=2.0, n=4, stage="hash", metric="header_hash_per_sec",
    run="r1", fp=FP, provenance="bench", params=None, t=1000.0,
):
    """Synthetic canonical record around a target median/MAD."""
    half = n // 2
    samples = [median - mad] * half + [median + mad] * (n - half)
    if n % 2:
        samples[-1] = median  # odd n: keep the median exact
    r = make_record(
        stage, metric, "u/s", samples, run_id=run, t=t, params=params,
        provenance=provenance, fingerprint=fp,
    )
    # pin the intended stats exactly (the list construction above is
    # close; the compare cases want precise medians)
    r["median"], r["mad"] = float(median), float(mad)
    return r


# ------------------------------------------------------------ harness


def test_median_mad():
    med, mad = median_mad([10, 12, 11, 100])  # outlier-robust
    assert med == 11.5
    assert mad == 1.0
    with pytest.raises(ValueError):
        median_mad([])


def test_rate_samples_shape_and_units():
    s = rate_samples(lambda: 50, repeats=4, warmup=1, min_time=0.001)
    assert len(s) == 4 and s.warmup == 1
    assert s.median > 0 and s.mad >= 0
    assert "±" in s.format() and "n=4" in s.format()
    # returning a number scales the sample to units/s, not calls/s
    calls = rate_samples(lambda: None, repeats=2, warmup=0, min_time=0.001)
    units = rate_samples(lambda: 1000, repeats=2, warmup=0, min_time=0.001)
    assert units.median > calls.median * 10


# ------------------------------------------------------- record schema


def test_record_key_canonicalizes_params():
    a = rec(params={"flood": 1000, "mode": "batched"})
    b = rec(params={"mode": "batched", "flood": 1000})
    assert record_key(a) == record_key(b)
    assert record_key(a) == "hash/header_hash_per_sec?flood=1000,mode=batched"
    assert record_key(rec(params=None)) == "hash/header_hash_per_sec"


def test_fingerprint_id_excludes_git_rev_but_not_device():
    fp1 = dict(FP, git_rev="aaaa")
    fp2 = dict(FP, git_rev="bbbb")
    assert fp_id(fp1) == fp_id(fp2), "git rev must not break comparability"
    assert fp_id(FP) != fp_id(OTHER_FP), "device kind must break comparability"


def test_validate_record_rejects_bad_shapes():
    good = rec()
    validate_record(good)
    for mutation in (
        {"n": 0}, {"samples": "zap"}, {"median": "fast"},
        {"direction": "sideways"}, {"run": 7},
    ):
        bad = dict(good, **mutation)
        with pytest.raises(ValueError):
            validate_record(bad)


# ------------------------------------------------------------- ledger


def test_ledger_roundtrip_tolerates_torn_tail(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    records = [rec(run="r1"), rec(run="r2", median=110)]
    assert append_records(path, records) == 2
    # torn tail (SIGKILL mid-append), foreign JSON, wrong shape
    with open(path, "a") as f:
        f.write('["not", "a", "record"]\n')
        f.write('{"v": 1, "truncat')
    got = read_ledger(path)
    assert [r["run"] for r in got] == ["r1", "r2"]
    assert got[0]["median"] == 100.0


def test_latest_run_skips_backfill(tmp_path):
    records = [
        rec(run="smoke-1"),
        rec(run="BENCH_r01", provenance="backfill", fp=None),
    ]
    assert set(run_groups(records)) == {"smoke-1", "BENCH_r01"}
    run_id, latest = latest_run(records)
    assert run_id == "smoke-1", "a backfill import must never be the gated run"
    assert latest[0]["run"] == "smoke-1"
    assert latest_run([])[0] is None


def test_bless_refuses_backfill_and_writes_floors(tmp_path):
    records = [
        rec(run="r9"),
        rec(run="BENCH_r01", provenance="backfill", fp=None, metric="other"),
    ]
    out = bless(records, {}, note="pr-12")
    assert list(out) == [record_key(records[0])]
    entry = out[record_key(records[0])]
    assert entry["median"] == 100.0 and entry["fp"] == FP["fp"]
    path = str(tmp_path / "baselines.json")
    save_baselines(path, out)
    assert load_baselines(path) == out
    assert load_baselines(str(tmp_path / "missing.json")) == {}


# ------------------------------------------------------- compare math


def base_entry(median=100.0, mad=2.0, n=4, fp=FP, params=None):
    return bless([rec(median=median, mad=mad, n=n, fp=fp, params=params)], {})[
        record_key(rec(params=params))
    ]


def test_identical_rerun_does_not_trip():
    # same code, same box: candidate within noise of the baseline —
    # the gate must NOT cry wolf on a re-run
    base = base_entry()
    c = compare_to_baseline(rec(median=98.0, run="r2"), base)
    assert c["status"] == "ok", c
    c = compare_to_baseline(rec(median=103.0, run="r2"), base)
    assert c["status"] == "ok", c


def test_injected_30pct_slowdown_trips_naming_stage_and_delta():
    base = base_entry()
    c = compare_to_baseline(rec(median=70.0, run="r2"), base)
    assert c["status"] == "regression"
    assert "30.0% slower" in c["reason"]
    assert c["stage"] == "hash" and c["drop_frac"] == pytest.approx(0.30)


def test_noisy_box_inflates_threshold():
    # MAD 8 on a median of 100 at n=4: 5 standard errors of the
    # median ~= 5 * 1.4826 * 8 / (100 * sqrt(4)) = 29.7% — a 25% drop
    # is within box noise, NOT a regression
    base = base_entry(mad=8.0)
    c = compare_to_baseline(rec(median=75.0, mad=8.0, run="r2"), base)
    assert c["status"] == "ok"
    assert c["threshold_frac"] == pytest.approx(0.297, abs=0.01)
    # but MORE repetitions tighten the threshold: the same 25% drop
    # at n=16 is a confirmed regression (sqrt-k scaling)
    c = compare_to_baseline(rec(median=75.0, mad=8.0, n=16, run="r2"),
                            base_entry(mad=8.0, n=16))
    assert c["status"] == "regression"


def test_small_sample_refusal():
    base = base_entry()
    c = compare_to_baseline(rec(median=50.0, n=2, run="r2"), base)
    assert c["status"] == "refused"
    assert "insufficient samples" in c["reason"]
    # and a small-sample BASELINE refuses too
    c = compare_to_baseline(rec(median=50.0, run="r2"), base_entry(n=2))
    assert c["status"] == "refused"


def test_cross_fingerprint_demotes_to_informational():
    base = base_entry()
    c = compare_to_baseline(rec(median=40.0, fp=OTHER_FP, run="r2"), base)
    assert c["status"] == "informational"
    assert "cross-fingerprint" in c["reason"]
    # unknown fingerprint (backfill) likewise
    c = compare_to_baseline(
        rec(median=40.0, fp=None, provenance="backfill", run="r2"), base
    )
    assert c["status"] == "informational"
    assert "unknown fingerprint" in c["reason"]


def test_improvement_and_lower_better_direction():
    base = base_entry()
    c = compare_to_baseline(rec(median=150.0, run="r2"), base)
    assert c["status"] == "improved"
    lower = rec(median=150.0, run="r2")
    lower["direction"] = "lower_better"
    c = compare_to_baseline(lower, base)
    assert c["status"] == "regression", "lower_better flips the drop sign"


def test_compare_run_and_coverage_gaps():
    base = bless([rec(), rec(metric="merkle_root_per_sec")], {})
    run = [rec(run="r2")]  # merkle went silent
    comps = compare_run(run, base)
    assert [c["status"] for c in comps] == ["ok"]
    gaps = coverage_gaps(run, base)
    assert gaps == ["hash/merkle_root_per_sec"]


# -------------------------------------------------- lens gate folding


def test_lens_perf_regression_gate_trips_and_names_stage(tmp_path):
    from tendermint_tpu.lens.analyze import analyze_run

    run = tmp_path / "bench"
    run.mkdir()
    base = bless([rec(run="r1")], {})
    save_baselines(str(run / "baselines.json"), base)
    append_records(str(run / "ledger.jsonl"), [rec(run="r2", median=65.0)])
    report = analyze_run(str(run))
    gate = next(g for g in report["gates"] if g["name"] == "perf_regression")
    assert not gate["ok"]
    assert "hash/header_hash_per_sec" in gate["detail"]
    assert "35.0% slower" in gate["detail"]
    # healthy rerun passes, and the report carries the perf block
    append_records(str(run / "ledger.jsonl"), [rec(run="r3", median=99.0)])
    report = analyze_run(str(run))
    gate = next(g for g in report["gates"] if g["name"] == "perf_regression")
    assert gate["ok"], gate
    assert report["perf"]["latest_run"] == "r3"
    assert report["perf"]["comparisons"][0]["status"] == "ok"
    # gate thresholds are regular gate config (overridable per run)
    report = analyze_run(str(run), gates={"perf_min_rel_delta": 0.001,
                                          "perf_noise_mads": 0.01})
    gate = next(g for g in report["gates"] if g["name"] == "perf_regression")
    assert not gate["ok"], "tightened thresholds must reach the compare"


def test_lens_perf_gate_vacuous_without_ledger_and_names_unreadable(tmp_path):
    from tendermint_tpu.lens.analyze import analyze_run

    run = tmp_path / "empty"
    run.mkdir()
    report = analyze_run(str(run))
    gate = next(g for g in report["gates"] if g["name"] == "perf_regression")
    assert gate["ok"] and "no perf ledger" in gate["detail"]
    # unreadable ledger: still vacuous (evidence loss is not a perf
    # regression) but the detail must name the artifact, not claim
    # tmperf was off — the lockcheck precedent
    (run / "ledger.jsonl").mkdir()
    report = analyze_run(str(run))
    gate = next(g for g in report["gates"] if g["name"] == "perf_regression")
    assert gate["ok"] and "unreadable" in gate["detail"]


def test_analyze_run_prefers_persisted_env_fingerprint(tmp_path):
    from tendermint_tpu.lens.analyze import analyze_run

    run = tmp_path / "run"
    run.mkdir()
    report = analyze_run(str(run))
    assert report["fingerprint"]["source"] == "analyzer"
    persisted = dict(FP, device="tpu:TPU v9000")
    with open(run / "env_fingerprint.json", "w") as f:
        json.dump(persisted, f)
    report = analyze_run(str(run))
    assert report["fingerprint"]["device"] == "tpu:TPU v9000"
    assert "source" not in report["fingerprint"]


# ---------------------------------------------------------------- CLI


def _tmperf_main():
    spec = importlib.util.spec_from_file_location(
        "tmperf_cli", os.path.join(_ROOT, "scripts", "tmperf.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


@pytest.fixture
def recording_in_process():
    """`tmperf record` with every stage, in this process: its last stage
    (device-obs) brings up jax's backend and installs the device
    observatory, and nothing takes either back. The backend is brought
    up first, so that every record of the test carries one fingerprint
    whatever ran on this worker before it, and the observatory is left
    as it was found (tests/test_devobs.py expects it uninstalled)."""
    import jax

    from tendermint_tpu import devobs

    jax.devices()
    was_installed = devobs.enabled()
    yield
    if not was_installed:
        devobs.uninstall()


def test_cli_rc_contract_record_bless_gate_trend(tmp_path, capsys, recording_in_process):
    main = _tmperf_main()
    ledger = str(tmp_path / "ledger.jsonl")
    baselines = str(tmp_path / "baselines.json")
    fast = ["--repeats", "3", "--min-time", "0.01", "--flood", "100",
            "--ledger", ledger]
    # record two baseline-able runs
    assert main(["record", *fast]) == 0
    assert main(["bless", "--ledger", ledger, "--baselines", baselines]) == 0
    assert main(["record", *fast]) == 0
    # unchanged code back-to-back: generous smoke floor => rc 0
    assert main(["gate", "--ledger", ledger, "--baselines", baselines,
                 "--min-rel-delta", "0.8"]) == 0
    # injected slowdown: rc 1, stderr/stdout names the stage + delta
    assert main(["record", *fast, "--inject", "hash:0.9"]) == 0
    capsys.readouterr()
    assert main(["gate", "--ledger", ledger, "--baselines", baselines,
                 "--min-rel-delta", "0.3"]) == 1
    out = capsys.readouterr()
    assert "hash/" in out.out and "% slower" in out.out
    assert "PERF REGRESSION" in out.err
    # --check drift: a run missing a blessed stage fails loudly
    assert main(["record", *fast, "--stages", "mempool"]) == 0
    capsys.readouterr()
    assert main(["gate", "--check", "--ledger", ledger,
                 "--baselines", baselines, "--min-rel-delta", "0.8"]) == 1
    out = capsys.readouterr()
    assert "NO record" in out.out
    # trend renders every run
    assert main(["trend", "--ledger", ledger]) == 0
    out = capsys.readouterr().out
    assert "hash/header_hash_per_sec" in out and "smoke-" in out
    # usage / no-data paths
    assert main(["bogus"]) == 2
    assert main(["gate", "--ledger", str(tmp_path / "none.jsonl")]) == 2
    assert main(["record", "--stages", "warpdrive"]) == 2
    assert main(["compare", "--ledger", ledger, "--run", "no-such-run"]) == 2
    assert main([]) == 2


def test_cli_backfill_parses_bench_captures(tmp_path, capsys):
    main = _tmperf_main()
    bench_dir = tmp_path / "bench"
    bench_dir.mkdir()
    # a synthetic round capture shaped like the real BENCH_r* files:
    # concatenated JSON objects, rate lines buried in the tail
    round_obj = {
        "n": 5,
        "cmd": "python bench.py",
        "rc": 0,
        "tail": (
            "# [  584.4s] batch 256 msm: 66 sigs/s pipelined\n"
            "# [  585.1s] fast-sync: 10.6 blocks/s @1000 vals\n"
            '{"metric": "fast_sync_blocks_per_sec", "value": 10.6, '
            '"unit": "blocks/sec/chip @1000 validators", "vs_baseline": 0.91}\n'
            '{"metric": "ed25519_batch_verify_throughput", "value": 100.9, '
            '"unit": "sigs/sec/chip", "vs_baseline": 0.013}\n'
        ),
        "parsed": {
            "metric": "ed25519_batch_verify_throughput",
            "value": 100.9, "unit": "sigs/sec/chip", "vs_baseline": 0.013,
        },
    }
    with open(bench_dir / "BENCH_r05.json", "w") as f:
        json.dump(round_obj, f)
        json.dump({"n": 6, "rc": 1, "tail": "died"}, f)  # concatenated, barren
    ledger = str(tmp_path / "ledger.jsonl")
    assert main(["backfill", "--bench-dir", str(bench_dir), "--ledger", ledger]) == 0
    records = read_ledger(ledger)
    assert {(r["stage"], r["metric"]) for r in records} == {
        ("engine", "ed25519_batch_verify_throughput"),
        ("msm", "ed25519_msm_throughput"),
        ("fastsync", "fast_sync_blocks_per_sec"),
    }
    assert all(r["provenance"] == "backfill" and r["fp"] is None for r in records)
    assert all(r["run"] == "BENCH_r05" for r in records)
    msm = next(r for r in records if r["stage"] == "msm")
    assert msm["median"] == 66.0
    # params mapped to the LIVE bench record shapes, so trend connects
    # history to new runs (record_key includes params)
    assert msm["params"] == {"batch": 256, "cached": True}
    fsync = next(r for r in records if r["stage"] == "fastsync")
    assert fsync["params"] == {"validators": 1000}
    # backfilled history is informational-only: never a regression
    base = bless([rec(stage="engine", metric="ed25519_batch_verify_throughput",
                      median=4355.5, params=None)], {})
    comps = compare_run([r for r in records if r["stage"] == "engine"], base)
    assert comps[0]["status"] == "informational"
    # idempotent: the round is already in the ledger
    capsys.readouterr()
    assert main(["backfill", "--bench-dir", str(bench_dir), "--ledger", ledger]) == 0
    assert "already in ledger" in capsys.readouterr().out
    assert len(read_ledger(ledger)) == len(records)
    # no captures at all: rc 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert main(["backfill", "--bench-dir", str(empty)]) == 2


# ------------------------------------------------- smoke + isolation


def test_run_smoke_injection_and_validation(tmp_path):
    from perf_smoke import run_smoke

    ledger = str(tmp_path / "ledger.jsonl")
    run_id, records = run_smoke(
        stages=["hash"], repeats=3, min_time=0.01, ledger_path=ledger,
        run_id="clean",
    )
    _, slowed = run_smoke(
        stages=["hash"], repeats=3, min_time=0.01, ledger_path=ledger,
        inject={"hash": 0.5}, run_id="slowed",
    )
    by_key = {record_key(r): r for r in records}
    for r in slowed:
        clean = by_key[record_key(r)]
        assert r["median"] < clean["median"] * 0.75, (
            "a 50% injection must land far below the clean run"
        )
        assert "injected" in r["note"]
    assert len(read_ledger(ledger)) == len(records) + len(slowed)
    with pytest.raises(ValueError, match="unknown smoke stages"):
        run_smoke(stages=["warpdrive"], ledger_path=ledger)


def test_perf_plane_import_isolation():
    """perf/ joins the lens/flight/check isolated plane: importable
    with zero jax and zero node runtime (two-way guard like
    test_lens/test_series)."""
    code = (
        "import sys\n"
        "import tendermint_tpu.perf\n"
        "import tendermint_tpu.perf.trend\n"
        "bad = [m for m in sys.modules if m.startswith('jax')]\n"
        "bad += [m for m in sys.modules if m.startswith('tendermint_tpu.') and\n"
        "        m.split('.')[1] not in ('perf', 'utils')]\n"
        "assert not bad, f'perf pulled in {bad}'\n"
        "print('ISOLATED')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=_ROOT, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr
    assert "ISOLATED" in out.stdout
