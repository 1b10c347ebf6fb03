"""tmproof slow acceptance: hundreds of concurrent bisecting light
clients against a live 4-node kill/pause net (ISSUE 15).

Every client is a REAL LightClient over the keep-alive HTTPProvider:
it initializes a trust root, bisection-verifies the chain head through
the one-round-trip `light_batch` route, fetches batched tx multiproofs
via `proofs_batch`, and verifies each multiproof against the
LIGHT-VERIFIED header's data_hash — never the primary's self-reported
root. The run is live-gated by the tmwatch rolling proof gates
(proof_serve_p99 windowed p99 + the opt-in proof_rate_stall), and the
post-run verdict plane must PASS with the proof_serve_p99 gate judged
on real serve evidence, every node's ProofMetrics nonzero in
fleet_report.json.

Kill/pause-only per the core gate in e2e/scenario.py (and the memory
note: partition/disconnect redial storms starve 2-core boxes).
"""

from __future__ import annotations

import hashlib
import os
import threading
import time

import pytest

from tendermint_tpu.e2e.manifest import Manifest
from tendermint_tpu.e2e.runner import Runner
from tendermint_tpu.e2e.scenario import gate_overrides_for
from tendermint_tpu.light import LightClient, TrustOptions
from tendermint_tpu.light.http_provider import HTTPProvider
from tendermint_tpu.rpc.client import RPCClientError
from tendermint_tpu.rpc.core import multiproof_from_json

N_CLIENTS = 120
CHAIN = "proofs-net"

_MANIFEST = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "e2e-manifests", "proofs.toml",
)


class _BisectingClient(threading.Thread):
    """One light client: trust-root init, then a verify-head +
    fetch-proofs loop until told to stop. Transient errors (its primary
    is being killed/paused mid-scenario) are counted and retried;
    anything else aborts the thread and fails the test."""

    def __init__(self, cid: int, rpc_url: str, stop: threading.Event):
        super().__init__(daemon=True, name=f"light-client-{cid}")
        self.cid = cid
        self.rpc_url = rpc_url
        self.stop_evt = stop
        self.verified_heads = 0
        self.proofs_verified = 0
        self.transient_errors = 0
        self.fatal: BaseException | None = None

    def run(self):
        try:
            self._run()
        except BaseException as e:  # noqa: BLE001 - surfaced by the test body
            self.fatal = e

    def _client(self) -> LightClient:
        provider = HTTPProvider(CHAIN, self.rpc_url, timeout=15.0)
        lb1 = provider.light_block(1)
        opts = TrustOptions(
            period_ns=3600 * 10**9, height=1, hash=lb1.signed_header.hash()
        )
        return LightClient(CHAIN, opts, provider)

    def _run(self):
        lc = None
        while not self.stop_evt.is_set():
            try:
                if lc is None:
                    lc = self._client()
                head = lc.update()  # bisection-verifies to the primary head
                if head is not None:
                    self.verified_heads += 1
                    self._fetch_and_verify_proofs(lc, head)
            except AssertionError:
                raise  # a proof that failed verification is never transient
            except Exception:  # noqa: BLE001
                # a killed/paused primary mid-request is the scenario
                # working as intended; the client retries like a real one
                self.transient_errors += 1
                if self.stop_evt.wait(0.5):
                    return
                continue
            self.stop_evt.wait(0.1 + (self.cid % 7) * 0.05)

    def _fetch_and_verify_proofs(self, lc: LightClient, head) -> None:
        """Try the head and up to two heights below it (verified via
        the light client's backwards hash-chain walk) until one carries
        txs, then verify its multiproof against the VERIFIED header's
        data_hash — never the primary's self-reported root."""
        import base64

        provider: HTTPProvider = lc.primary
        for h in range(head.height, max(head.height - 3, 0), -1):
            try:
                res = provider.client.call("proofs_batch", height=h, indices=[0])
            except RPCClientError as e:
                if e.code == -32602:
                    continue  # empty block at this height: nothing to prove
                raise
            lb = head if h == head.height else lc.verify_light_block_at_height(h)
            mp = multiproof_from_json(res["multiproof"])
            txs = [base64.b64decode(t) for t in res["txs"]]
            want = lb.signed_header.header.data_hash  # the VERIFIED root
            assert mp.verify(want, [hashlib.sha256(tx).digest() for tx in txs]), (
                f"client {self.cid}: multiproof at height {h} does not "
                "verify against the light-verified data_hash"
            )
            self.proofs_verified += len(mp.indices)
            return


@pytest.mark.slow
def test_proof_gateway_under_concurrent_bisecting_clients(tmp_path):
    with open(_MANIFEST) as f:
        m = Manifest.parse(f.read())
    assert all(set(n.perturb) <= {"kill", "pause"} for n in m.nodes), (
        "proofs.toml must stay kill/pause-only (core-gate rule)"
    )
    runner = Runner(m, str(tmp_path / "net"), logger=lambda *a: None)
    # the small-box host-crypto pin (run_soak discipline): node
    # processes must not burn the cores on jax imports mid-scenario
    for k, v in (("TM_TPU_CRYPTO", "off"), ("TM_TPU_AUTOTUNE", "off")):
        runner.extra_node_env.setdefault(k, os.environ.get(k, v))
    post_gates, watch_gates = gate_overrides_for()
    # tmproof rolling gates, opted in for the whole client window: the
    # serve p99 budget is the default; the stall gate may only run
    # while clients are guaranteed to keep asking
    watch_gates = dict(watch_gates, proof_stall_after_s=90.0)
    runner.setup()
    stop = threading.Event()
    clients: list[_BisectingClient] = []
    try:
        runner.start(timeout=120)
        runner.start_watch(gates=watch_gates)
        runner.wait_for_height(2, timeout=120)

        def _load_forever():
            # paced tx load for the WHOLE client window, so most
            # committed heights carry a provable (non-empty) tx tree
            while not stop.is_set():
                try:
                    runner.inject_load(10.0)
                except Exception:  # noqa: BLE001 - perturbed RPC: retry
                    time.sleep(1.0)

        load = threading.Thread(target=_load_forever, daemon=True, name="proof-load")
        load.start()
        targets = runner._rpc_nodes()
        for cid in range(N_CLIENTS):
            c = _BisectingClient(cid, targets[cid % len(targets)].rpc_url, stop)
            clients.append(c)
            c.start()
        # phase A: EVERY client finishes verified (trust root + at
        # least one bisection-verified head) under full concurrency,
        # before any fault lands
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            runner.check_watch()
            if all(c.verified_heads >= 1 for c in clients):
                break
            time.sleep(0.5)
        assert all(c.verified_heads >= 1 for c in clients), sorted(
            (c.cid, c.verified_heads) for c in clients if c.verified_heads < 1
        )
        pre_fault = sum(c.verified_heads for c in clients)
        # kill/pause scenario with all clients still hammering the
        # gateway (their primaries vanish mid-bisection and come back)
        runner.run_perturbations()
        # phase B: post-heal recovery judged as AGGREGATE progress — on
        # the 1-core CI box a convoy of 120 clients cannot all finish
        # another full bisection promptly, but the fleet as a whole
        # must keep verifying through the healed net
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            runner.check_watch()
            if sum(c.verified_heads for c in clients) >= pre_fault + N_CLIENTS // 2:
                break
            time.sleep(0.5)
        post_heal_progress = sum(c.verified_heads for c in clients) - pre_fault
        stop.set()
        load.join(timeout=60)
        for c in clients:
            c.join(timeout=30)
        # convergence judged by the runner's own timeouts: evaluation
        # holds (scrapes continue) since the proof load has ended and
        # the opt-in stall gate would read "clients finished" as a wedge
        runner.hold_watch()
        h = max(n.height() for n in runner._rpc_nodes())
        runner.wait_for_height(h + 2, timeout=120)
        runner.check_consistency()
    finally:
        stop.set()
        runner.cleanup()
        if post_gates and runner.nodes and os.path.isdir(runner.base_dir):
            runner.analyze_artifacts(gates=post_gates)

    # every client finished VERIFIED (phase A asserted >= 1 each), no
    # fatal errors anywhere, and the fleet kept verifying after the
    # faults healed
    fatals = [(c.cid, c.fatal) for c in clients if c.fatal is not None]
    assert not fatals, fatals
    assert post_heal_progress >= N_CLIENTS // 2, (
        f"only {post_heal_progress} verified heads across the fleet after the "
        "kill/pause faults healed"
    )
    # the client-side count is contention-coupled (how many iterations
    # each of 120 threads completes on a 1-core box varies run to run);
    # the floor proves the fetch-and-verify path ran BROADLY — the
    # per-node served assertions below are the fleet-side coverage
    total_proofs = sum(c.proofs_verified for c in clients)
    assert total_proofs >= N_CLIENTS // 4, (
        f"only {total_proofs} multiproof-verified tx proofs across "
        f"{N_CLIENTS} clients — the tx load should make most heights provable"
    )

    # full gate plane PASS, proof_serve_p99 judged on real evidence
    report = runner.last_report
    assert report is not None and report["verdict"] == "pass", (
        report and report["gates"]
    )
    gate = next(g for g in report["gates"] if g["name"] == "proof_serve_p99")
    assert gate["ok"] and "idle" not in gate["detail"], gate
    assert report["fleet"]["proofs"]["served_total"] > 0
    assert report["fleet"]["proofs"]["serve_p99_s"] is not None

    # per-node ProofMetrics nonzero in fleet_report: every consensus
    # node served proofs (clients are pinned round-robin)
    for s in report["nodes"]:
        pf = s.get("proofs")
        assert pf and pf["served_total"] > 0, (s["name"], pf)
        assert pf["serve"] and pf["serve"]["count"] > 0, (s["name"], pf)
        # the hot-tree cache carried repeat requests
    assert sum(
        (s["proofs"]["tree_cache"]["hit"] for s in report["nodes"] if s.get("proofs")),
    ) > 0, "no node's hot-tree cache recorded a hit under repeated proof requests"
