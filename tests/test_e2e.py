"""E2E harness tests: multi-process testnet with perturbations
(ref: test/e2e/runner + test/e2e/tests)."""

from __future__ import annotations

import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(__file__))

from tendermint_tpu.e2e import Manifest, Runner, WatchTripped

MANIFEST = """
chain_id = "e2e-test"
load_tx_rate = 15
vote_extensions_enable_height = 2

[node.validator01]
perturb = ["kill"]

[node.validator02]
perturb = ["pause"]

[node.validator03]
abci_protocol = "grpc"

[node.validator04]
abci_protocol = "tcp"
perturb = ["disconnect"]

[validator_update.3]
validator03 = 250
"""


def test_manifest_parse():
    m = Manifest.parse(MANIFEST)
    assert m.chain_id == "e2e-test"
    assert len(m.nodes) == 4 and len(m.validators) == 4
    assert m.vote_extensions_enable_height == 2
    assert m.nodes[0].perturb == ["kill"]
    assert m.nodes[2].abci_protocol == "grpc"
    assert m.nodes[3].abci_protocol == "tcp"
    assert m.validator_updates == {3: {"validator03": 250}}


@pytest.mark.slow
def test_e2e_perturbed_testnet(tmp_path):
    """Full cycle: 4 validator processes (one behind an out-of-process
    socket app, one behind a gRPC app), tx load, duplicate-vote evidence
    injected and committed, a scheduled validator power update taking
    effect on-chain, kill + pause perturbations, consistency + cadence
    checks."""
    m = Manifest.parse(MANIFEST)
    runner = Runner(m, str(tmp_path / "net"), logger=lambda *a: None)
    runner.setup()
    try:
        runner.start(timeout=120)
        runner.wait_for_height(2, timeout=120)
        load = threading.Thread(target=runner.inject_load, args=(8.0,), daemon=True)
        load.start()
        ev_hash = runner.inject_evidence(timeout=90)
        assert ev_hash
        runner.apply_validator_updates(timeout=90)
        runner.run_perturbations()
        load.join(timeout=30)
        h = max(n.height() for n in runner.nodes)
        runner.wait_for_height(h + 2, timeout=120)
        runner.check_consistency()
        bench = runner.benchmark()
        assert bench["blocks"] >= 3
        assert bench["avg_interval_s"] is not None
        # every node holds load txs: query one committed kv pair
        client = runner.nodes[2].client()
        res = client.call("abci_info")
        assert int(res["response"]["last_block_height"]) >= 2
    finally:
        runner.cleanup()
    # cleanup scraped each node's final /metrics exposition into its
    # home dir; every commit is verified through the engine, so the
    # commit-verify traffic must have surfaced the engine telemetry
    # plane (ops/engine.py -> metrics.EngineMetrics via the process-
    # global registry) on at least one node's scrape.
    scraped = []
    for node in runner.nodes:
        path = os.path.join(node.home, "metrics.txt")
        if os.path.exists(path):
            with open(path) as f:
                scraped.append(f.read())
    assert scraped, "no node produced a metrics.txt artifact"
    assert any("tendermint_consensus_height" in t for t in scraped)
    assert any("tendermint_engine_submitted_jobs_total" in t for t in scraped), (
        "engine telemetry series missing from every node's final scrape"
    )
    # the structural-hash plane (crypto/merkle + the memoized
    # ValidatorSet/Header hashes) rides the same process-global
    # registry; any committed block must have produced builds and memo
    # events with nonzero values
    assert any(
        "tendermint_hash_merkle_builds_total" in t
        and "tendermint_hash_cache_events_total" in t
        for t in scraped
    ), "hash-plane telemetry series missing from every node's final scrape"
    # ROADMAP-4 gate (tmlens, PR 8): cleanup ran the fleet analyzer over
    # the collected artifacts. A perturbed-but-recovered run must yield
    # a PASSING verdict — fresh chain heads, bounded height spread, step
    # p99 within budget, all required series present — and the machine-
    # checkable report must be on disk next to the node dirs.
    assert runner.last_report is not None, "tmlens analysis did not run in cleanup"
    assert runner.last_report["verdict"] == "pass", runner.last_report["gates"]
    assert os.path.exists(os.path.join(runner.base_dir, "fleet_report.json"))
    gate_names = {g["name"] for g in runner.last_report["gates"]}
    assert gate_names == {
        "liveness_stall", "p99_step_duration", "height_spread", "missing_series",
        "rate_stall", "churn_storm", "journey_stall", "lock_order_cycle",
        "shared_state_race", "perf_regression", "proof_serve_p99",
        "evidence_committed", "recompile_storm", "device_mem_growth",
    }
    # tmperf fingerprint surfacing: the runner persisted the run-time
    # environment fingerprint and the report carries it (slow box vs
    # slow build is a report field, not an XLA-error-tail excavation)
    assert os.path.exists(os.path.join(runner.base_dir, "env_fingerprint.json"))
    assert runner.last_report["fingerprint"]["cores"] == os.cpu_count()
    assert "source" not in runner.last_report["fingerprint"], (
        "the report must carry the RUN-time fingerprint artifact, "
        "not an analyzer-host fallback"
    )
    # the kill perturbation snapshotted the victim's pre-death state
    killed = next(n for n in runner.nodes if "kill" in n.m.perturb)
    assert os.path.exists(os.path.join(killed.home, "metrics.pre-kill.txt")), (
        "perturb(kill) left no pre-death artifact snapshot"
    )
    # origin-stamped gossip: every node must have recorded nonzero
    # propagation samples (consensus_msg_propagation_seconds) — a
    # healthy net gossips proposals/votes continuously
    for text in scraped:
        assert "tendermint_consensus_msg_propagation_seconds_count" in text, (
            "a node's scrape lacks gossip-propagation samples"
        )
    # flight recorder (manifest default 1s): each node streamed delta
    # records as the run progressed; the record count must be of the
    # same order as run duration / flight-interval (the kill victim's
    # first life and SIGSTOP pauses cost some ticks)
    from tendermint_tpu.lens.series import parse_timeseries

    for node in runner.nodes:
        ts = os.path.join(node.home, "timeseries.jsonl")
        assert os.path.exists(ts), f"{node.m.name} left no timeseries.jsonl"
        assert len(parse_timeseries(ts)) >= 5, f"{node.m.name} timeline too short"
    # the per-node timelines made it into the fleet report
    assert runner.last_report["fleet"]["nodes_with_timeseries"] >= 1


@pytest.mark.slow
def test_e2e_ci_live_critical_path(tmp_path, monkeypatch):
    """The tmpath acceptance run, on the kill/pause-only live manifest
    (e2e-manifests/ci-live.toml — partition/disconnect redial storms
    starve 2-core boxes; memory note): a live 4-node run with tracing
    and the live watch on must produce a fleet_report.json whose
    critical_path block decomposes every committed height on every
    node into proposer/gossip/verify/quorum/apply summing to within
    15% of the measured block interval, and a merged Perfetto trace
    with at least one cross-node journey flow per committed height."""
    manifest_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "e2e-manifests", "ci-live.toml",
    )
    with open(manifest_path) as f:
        m = Manifest.parse(f.read())
    assert all(set(n.perturb) <= {"kill", "pause"} for n in m.nodes), (
        "ci-live.toml must stay kill/pause-only (2-core redial-storm note)"
    )
    monkeypatch.setenv("TM_TPU_TRACE", "1")  # runner env propagates to nodes
    # lockcheck acceptance rides the same run (docs/static-analysis.md
    # #lockcheck): every node boots with the lock sanitizer on, the
    # verdict must stay pass with zero order-inversion cycles, and the
    # estimated sanitizer overhead must stay within 1% of wall-clock
    monkeypatch.setenv("TM_TPU_LOCKCHECK", "1")
    # racecheck acceptance too (docs/static-analysis.md#racecheck):
    # the Eraser lockset sanitizer shims the hot classes fleet-wide;
    # zero shared_state_race events, and the COMBINED per-node
    # sanitizer overhead (lockcheck + racecheck) stays within 2%
    monkeypatch.setenv("TM_TPU_RACECHECK", "1")
    runner = Runner(m, str(tmp_path / "net"), logger=lambda *a: None)
    runner.setup()
    t_run0 = time.monotonic()
    try:
        runner.start(timeout=120)
        runner.start_watch()
        runner.wait_for_height(2, timeout=120)
        load = threading.Thread(target=runner.inject_load, args=(8.0,), daemon=True)
        load.start()
        runner.run_perturbations()
        load.join(timeout=30)
        h = max(n.height() for n in runner.nodes)
        runner.wait_for_height(h + 2, timeout=120)
        runner.check_consistency()
    finally:
        wall_s = time.monotonic() - t_run0
        runner.cleanup()
    report = runner.last_report
    assert report is not None and report["verdict"] == "pass", (
        report and report["gates"]
    )
    # lockcheck: artifacts from every node, gate judged on real
    # evidence (not the vacuous pass), no cycles, overhead <= 1%
    lock_gate = next(g for g in report["gates"] if g["name"] == "lock_order_cycle")
    assert lock_gate["ok"] and "TM_TPU_LOCKCHECK off" not in lock_gate["detail"], lock_gate
    lc_fleet = report["fleet"]["lockcheck"]
    assert report["fleet"]["nodes_with_lockcheck"] >= 4
    assert lc_fleet["cycles"] == 0, lc_fleet
    # overhead budget is PER PROCESS (each node pays its own sanitizer
    # tax against its own lifetime; the fleet sum divided by one
    # wall-clock would scale with node count, not cost). Since PR 13
    # the acceptance budget is the COMBINED lockcheck+racecheck 2%
    # below — both sanitizers always ride this run together, and the
    # old solo-1% line sat within calibration noise of a loaded 2-core
    # box (per-op cost is measured at exit while 4 nodes tear down)
    per_node = [
        (s["name"], s["lockcheck"]["overhead_s_est"])
        for s in report["nodes"] if s.get("lockcheck")
    ]
    assert per_node and all(o is not None for _n, o in per_node), per_node
    # racecheck: artifacts from every node, gate judged on real
    # evidence, zero shared-state races, and the COMBINED sanitizer
    # overhead (lock shim + race shim, per process) within 2%
    race_gate = next(g for g in report["gates"] if g["name"] == "shared_state_race")
    assert race_gate["ok"] and "TM_TPU_RACECHECK off" not in race_gate["detail"], race_gate
    assert report["fleet"]["nodes_with_racecheck"] >= 4
    assert report["fleet"]["racecheck"]["races"] == 0, report["fleet"]["racecheck"]
    combined = [
        (s["name"], s["lockcheck"].get("overhead_s_est"),
         s["racecheck"].get("overhead_s_est"))
        for s in report["nodes"]
        if s.get("lockcheck") and s.get("racecheck")
    ]
    assert len(combined) >= 4 and all(
        lo is not None and ro is not None for _n, lo, ro in combined
    ), combined
    worst_combined = max(combined, key=lambda p: p[1] + p[2])
    assert worst_combined[1] + worst_combined[2] <= 0.02 * wall_s, (
        worst_combined, wall_s, combined)
    # per-node critical paths: every committed height decomposed, the
    # stages tiling the measured interval within the 15% tolerance
    # (anchors judged from partial evidence are flagged, not asserted:
    # the kill victim's first life took its ring with it)
    from tendermint_tpu.lens.journey import STAGES

    nodes_with_paths = 0
    full_heights = 0
    for s in report["nodes"]:
        cp = s.get("critical_path")
        assert cp, f"{s['name']} left no critical_path (tracing env lost?)"
        nodes_with_paths += 1
        anchors = s["trace"]["anchor_heights"]
        committed = set(range(anchors[0], anchors[1] + 1))
        assert committed <= {int(h) for h in cp["heights"]}, (
            s["name"], anchors, sorted(cp["heights"]))
        for h, e in cp["heights"].items():
            total = sum(e["stages"][st] for st in STAGES)
            # abs floor: per-stage µs rounding on a near-zero interval
            # (WAL-replayed heights) must not read as a 15% miss
            assert total == pytest.approx(e["interval_s"], rel=0.15, abs=1e-4), (
                s["name"], h, e)
            if "missing" not in e:
                full_heights += 1
    assert nodes_with_paths == 4 and full_heights >= 4
    gate = next(g for g in report["gates"] if g["name"] == "journey_stall")
    assert gate["ok"], gate
    # fleet digest present and spanning the chain
    fcp = report["fleet"]["critical_path"]
    assert fcp["nodes"] == 4 and fcp["heights_covered"] >= 3
    # the merged trace draws >= 1 cross-node journey flow per height
    # the fleet committed while >= 2 nodes were traced
    import json as _json

    from tendermint_tpu.lens.journey import journey_height

    with open(os.path.join(runner.base_dir, "fleet_trace.json")) as f:
        doc = _json.load(f)
    flow_heights = {
        journey_height(e["id"])
        for e in doc["traceEvents"]
        if e.get("cat") == "tm.journey" and e.get("ph") == "s"
    } - {None}
    lo = min(int(h) for s in report["nodes"]
             for h in (s.get("critical_path") or {}).get("heights", {}))
    hi = max(int(h) for s in report["nodes"]
             for h in (s.get("critical_path") or {}).get("heights", {}))
    covered = set(range(lo + 1, hi + 1))  # h=lo may predate every trace ring
    assert covered <= flow_heights, sorted(covered - flow_heights)


STALL_MANIFEST = """
chain_id = "e2e-stall"
load_tx_rate = 5

[node.validator01]

[node.validator02]

[node.validator03]

[node.validator04]
"""


@pytest.mark.slow
def test_e2e_watch_aborts_on_injected_stall(tmp_path):
    """The tmwatch acceptance run: a liveness stall injected mid-run
    (SIGSTOP of half the validator set -> no quorum, heights freeze)
    must be detected by the LIVE collector and abort the run in well
    under half the old do-nothing timeout, with a full artifact sweep
    and a fleet report whose FAIL verdict names the gate."""
    import signal as _signal
    import time as _time

    m = Manifest.parse(STALL_MANIFEST)
    runner = Runner(m, str(tmp_path / "net"), logger=lambda *a: None)
    runner.setup()
    frozen = []
    try:
        runner.start(timeout=120)
        runner.wait_for_height(3, timeout=120)
        runner.start_watch(
            interval=1.0, gates={"stall_after_s": 12.0, "watch_window_s": 20.0}
        )
        # injected stall: freeze 2 of 4 validators — the survivors
        # cannot assemble a quorum, so the whole fleet's head goes stale
        frozen = runner.nodes[:2]
        for node in frozen:
            node.proc.send_signal(_signal.SIGSTOP)
        t0 = _time.monotonic()
        old_timeout = 120.0  # what a watchless run would burn
        with pytest.raises(WatchTripped) as ei:
            runner.wait_for_height(10_000, timeout=old_timeout)
        detect_s = _time.monotonic() - t0
        assert ei.value.gate == "liveness_stall", ei.value
        assert detect_s < old_timeout / 2, (
            f"abort took {detect_s:.0f}s, not under half the {old_timeout:.0f}s timeout"
        )
    finally:
        for node in frozen:
            try:
                node.proc.send_signal(_signal.SIGCONT)
            except Exception:  # noqa: BLE001 - teardown
                pass
        runner.cleanup()
    report = runner.last_report
    assert report is not None, "no fleet report after aborted run"
    assert report["verdict"] == "fail"
    assert report["live_abort"]["gate"] == "liveness_stall"
    gate = next(g for g in report["gates"] if g["name"] == "liveness_stall")
    assert not gate["ok"] and "live watch abort" in gate["detail"]
    # the trip-time sweep captured the survivors' state at the moment
    assert any(
        os.path.exists(os.path.join(n.home, "metrics.on-trip.txt"))
        for n in runner.nodes
    ), "watch trip left no on-trip artifact sweep"
    # flight recorders were on (e2e default): the stall is also in the
    # on-disk timelines, so a SIGKILL'd runner would still have dated it
    from tendermint_tpu.lens.series import parse_timeseries, summarize_timeseries

    tails = []
    for n in runner.nodes:
        ts = os.path.join(n.home, "timeseries.jsonl")
        if os.path.exists(ts):
            tl = summarize_timeseries(parse_timeseries(ts))
            if tl and tl.get("height"):
                tails.append(tl["height"]["stalled_tail_s"])
    assert tails and max(tails) >= 10.0, (
        f"stall not visible in flight-recorder timelines: {tails}"
    )


PARTITION_MANIFEST = """
chain_id = "e2e-part"
load_tx_rate = 5

[node.validator01]

[node.validator02]

[node.validator03]

[node.validator04]
perturb = ["partition"]
"""


@pytest.mark.slow
def test_e2e_asymmetric_partition(tmp_path):
    """VERDICT r4 item 7: transport-level per-link partition. The
    partitioned minority vetoes every peer (connections close and are
    refused per-link over real TCP), stalls with no quorum while the
    3/4 majority keeps committing, then heals and catches back up —
    verified by the runner's partition perturbation (stall + majority
    progress) plus post-heal progress and cross-node consistency."""
    m = Manifest.parse(PARTITION_MANIFEST)
    runner = Runner(m, str(tmp_path / "net"), logger=lambda *a: None)
    runner.setup()
    try:
        runner.start(timeout=120)
        runner.wait_for_height(2, timeout=120)
        runner.run_perturbations()  # includes stall + majority checks
        # post-heal: EVERY node reaches the post-partition height
        h = max(n.height() for n in runner.nodes)
        runner.wait_for_height(h + 1, timeout=120)
        runner.check_consistency()
    finally:
        runner.cleanup()


SEED_MANIFEST = """
chain_id = "e2e-seed"
load_tx_rate = 5

[node.seed01]
mode = "seed"

[node.validator01]

[node.validator02]

[node.validator03]
"""


@pytest.mark.slow
def test_e2e_seed_bootstrapped_testnet(tmp_path):
    """Validators know ONLY the seed's address (bootstrap_peers); PEX
    must discover the mesh across real processes and consensus must
    advance (ref: node/seed.go + pex reactor, e2e manifest seeds)."""
    m = Manifest.parse(SEED_MANIFEST)
    assert m.nodes[0].mode == "seed"
    runner = Runner(m, str(tmp_path / "net"), logger=lambda *a: None)
    runner.setup()
    # the topology really is seed-only: no validator lists peers
    from tendermint_tpu.config import load_config as _lc
    for node in runner.nodes[1:]:
        cfg = _lc(node.home)
        assert cfg.p2p.persistent_peers == ""
        assert runner.nodes[0].node_id in cfg.p2p.bootstrap_peers
    try:
        runner.start(timeout=120)
        runner.wait_for_height(3, timeout=120)
        runner.check_consistency()
    finally:
        runner.cleanup()


STATESYNC_MANIFEST = """
chain_id = "e2e-ss"
load_tx_rate = 10
snapshot_interval = 4

[node.validator01]

[node.validator02]

[node.full01]
mode = "full"
start_at = 10
state_sync = true
"""


@pytest.mark.slow
def test_e2e_statesync_late_join(tmp_path):
    """A node joining at height 10 with state_sync restores an app
    snapshot (trust root fetched from a live node's RPC) and then keeps
    up, instead of replaying from genesis (ref: e2e manifests'
    state_sync nodes + runner/setup.go)."""
    m = Manifest.parse(STATESYNC_MANIFEST)
    assert m.snapshot_interval == 4 and m.nodes[2].state_sync
    runner = Runner(m, str(tmp_path / "net"), logger=lambda *a: None)
    runner.setup()
    try:
        runner.start(timeout=180)  # includes the late joiner
        late = runner.nodes[2]
        # late node must catch up to the head
        head = max(n.height() for n in runner.nodes[:2])
        runner.wait_for_height(head + 2, nodes=[late], timeout=120)
        # proof it restored rather than replayed: its earliest stored
        # block is AFTER genesis (backfill window only)
        st = late.client().call("status")
        assert int(st["sync_info"]["earliest_block_height"]) > 1, st["sync_info"]
        runner.check_consistency()
    finally:
        runner.cleanup()


def test_delayed_app_and_manifest_delays():
    """Manifest ABCI delay fields (ref: manifest.go:80-86) parse and the
    delayed e2e app actually dallies the wrapped calls."""
    import time as _time

    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.e2e.app import DelayedKVStore

    m = Manifest.parse("""
chain_id = "d"
check_tx_delay_ms = 40
finalize_block_delay_ms = 25

[node.validator01]
""")
    assert m.check_tx_delay_ms == 40 and m.finalize_block_delay_ms == 25

    app = DelayedKVStore(delays_ms={"check_tx": 40})
    t0 = _time.perf_counter()
    app.check_tx(abci.RequestCheckTx(tx=b"a=1", type=0))
    assert _time.perf_counter() - t0 >= 0.04
    assert "finalize_block" not in app._delays  # undelayed call has no sleep
    # negative values are rejected at the runner boundary and ignored
    # defensively by the app wrapper
    assert DelayedKVStore(delays_ms={"check_tx": -40})._delays == {}


def test_generator_deterministic_and_valid():
    """ref: test/e2e/generator — seeded generation is reproducible and
    every emitted manifest satisfies the runner's invariants."""
    from tendermint_tpu.e2e.generator import generate, validate_generated

    a = generate(seed=7)
    b = generate(seed=7)
    assert a == b, "same seed must generate identical manifests"
    assert generate(seed=8) != a
    assert len(a) == 10  # 5 topologies x 2 abci modes
    for _, text in a:
        validate_generated(text)


def test_generator_covers_dimensions():
    """Across a seed sweep the generator exercises every axis: key
    types, ABCI transports, sync modes, perturbations, vote-extension
    heights, delays."""
    from tendermint_tpu.e2e.generator import generate, validate_generated

    key_types, protocols, perturbs, apps, modes = set(), set(), set(), set(), set()
    saw_statesync = saw_late = saw_vx = saw_delay = saw_update = False
    saw_retain = saw_scenario = False
    for seed in range(24):
        for _, text in generate(seed=seed):
            m = validate_generated(text)
            key_types.add(m.key_type)
            apps.add(m.app)
            saw_vx = saw_vx or m.vote_extensions_enable_height > 0
            saw_delay = saw_delay or m.finalize_block_delay_ms > 0
            saw_update = saw_update or bool(m.validator_updates)
            saw_retain = saw_retain or m.retain_blocks > 0
            saw_scenario = saw_scenario or bool(m.scenario)
            for n in m.nodes:
                modes.add(n.mode)
                protocols.add(n.abci_protocol)
                perturbs.update(n.perturb)
                saw_statesync = saw_statesync or n.state_sync
                saw_late = saw_late or n.start_at > 0
    assert key_types == {"ed25519", "secp256k1", "sr25519"}, key_types
    assert apps == {"kvstore", "bank"}, apps
    assert modes == {"validator", "full", "seed", "light"}, modes
    assert {"builtin", "tcp", "grpc", "unix"} <= protocols, protocols
    assert {"disconnect", "pause", "kill", "restart", "partition"} <= perturbs, perturbs
    assert saw_statesync and saw_late and saw_vx and saw_delay and saw_update
    assert saw_retain and saw_scenario


def test_generator_cli(tmp_path):
    from tendermint_tpu.cli import main as cli_main

    out = str(tmp_path / "manifests")
    assert cli_main(["e2e-generate", "--seed", "3", "--seeds", "2",
                     "--output", out]) == 0
    import os

    files = sorted(os.listdir(out))
    assert len(files) == 20 and all(f.endswith(".toml") for f in files)


@pytest.mark.slow
def test_generated_manifest_runs(tmp_path):
    """One generated manifest actually runs end to end — the generator's
    output is executable, not just parseable."""
    from tendermint_tpu.e2e.generator import generate

    # smallest generated net: the single-topology builtin manifest
    name, text = next(
        (n, t) for n, t in generate(seed=1) if "single-builtin" in n
    )
    m = Manifest.parse(text)
    runner = Runner(m, str(tmp_path / "net"), logger=lambda *a: None)
    runner.setup()
    try:
        runner.start(timeout=120)
        runner.wait_for_height(3, timeout=90)
        runner.check_consistency()
    finally:
        runner.cleanup()


SR_UPDATE_MANIFEST = """
chain_id = "e2e-sr-update"
key_type = "sr25519"
load_tx_rate = 5

[validator_update.3]
validator02 = 77

[node.validator01]

[node.validator02]
"""


@pytest.mark.slow
def test_e2e_sr25519_validator_update(tmp_path):
    """Regression: a validator power update on an sr25519 chain must
    take effect on-chain (the kvstore's val-change txs used to hardcode
    ed25519, silently no-op'ing on other key types)."""
    m = Manifest.parse(SR_UPDATE_MANIFEST)
    runner = Runner(m, str(tmp_path / "net"), logger=lambda *a: None)
    runner.setup()
    try:
        runner.start(timeout=120)
        runner.wait_for_height(2, timeout=120)
        runner.apply_validator_updates(timeout=90)
        vals = runner.nodes[0].client().call("validators")
        powers = {v["address"]: int(v["voting_power"]) for v in vals["validators"]}
        assert 77 in powers.values()
    finally:
        runner.cleanup()
