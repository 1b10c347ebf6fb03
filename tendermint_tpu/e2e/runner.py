"""E2E testnet runner (ref: test/e2e/runner/main.go, perturb.go, load.go,
benchmark.go).

Spawns one OS process per node (`python -m tendermint_tpu start`),
injects tx load, applies perturbations, waits for convergence, and
measures block cadence — the reference's docker-compose flow collapsed
onto one host with per-node home dirs and ports.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import statistics
import subprocess
import sys
import time

from ..config import default_config, load_config
from ..node import NodeKey
from ..privval import FilePV
from ..rpc.client import HTTPClient
from ..types.genesis import GenesisDoc, GenesisValidator
from ..utils.tmtime import Time
from .manifest import Manifest, NodeManifest


class WatchTripped(RuntimeError):
    """A live watch gate fired mid-run: the runner aborts instead of
    burning the remaining timeout. cleanup() still sweeps artifacts and
    the fleet report's verdict names this gate."""

    def __init__(self, gate: str, detail: str):
        super().__init__(f"live watch gate tripped: {gate} — {detail}")
        self.gate = gate
        self.detail = detail


def _free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


class E2ENode:
    def __init__(self, manifest: NodeManifest, home: str, p2p_port: int, rpc_port: int, abci_port: int, prom_port: int = 0):
        self.m = manifest
        self.home = home
        self.p2p_port = p2p_port
        self.rpc_port = rpc_port
        self.abci_port = abci_port
        self.prom_port = prom_port
        self.node_id = ""
        self.proc: subprocess.Popen | None = None
        self.app_proc: subprocess.Popen | None = None

    @property
    def rpc_url(self) -> str:
        return f"http://127.0.0.1:{self.rpc_port}"

    def client(self) -> HTTPClient:
        return HTTPClient(self.rpc_url, timeout=5.0)

    def height(self) -> int:
        try:
            return int(self.client().call("status")["sync_info"]["latest_block_height"])
        except Exception:
            return -1


class _BankSpigot:
    """Signed-transfer source for bank-app load (abci/bank.py).

    Every call mints a transfer to a FRESH random recipient — each one
    grows the account set, which is the point of the workload. Nonces
    are strictly sequential per sender, so the spigot:

      * funds its own WORKER account from the treasury at construction
        (purpose-keyed deterministic seed) — concurrent spigots (the
        load drip + a mid-run flood) then never race the treasury nonce;
      * hands a nonce out per call and takes it back via rollback()
        when the caller failed to submit the tx — only ACCEPTED
        submissions consume sequence numbers, otherwise one dropped tx
        would cascade BAD_NONCE failures through every later transfer.
    """

    FUNDING = 10_000_000

    def __init__(self, chain_id: str, client, purpose: str = "load"):
        import hashlib

        from ..abci.bank import make_transfer_tx, treasury_priv
        from ..crypto.ed25519 import Ed25519PrivKey

        self._make = make_transfer_tx
        self.chain_id = chain_id
        self.client = client
        seed = hashlib.sha256(
            f"tmsoak-bank-worker|{chain_id}|{purpose}".encode()
        ).digest()
        self.priv = Ed25519PrivKey.generate(seed=seed)
        self.nonce = self._committed_nonce(self.priv)
        self._last_committed = self.nonce
        if self._balance(self.priv) < self.FUNDING // 2:
            self._fund(treasury_priv(chain_id))

    # -- committed-state reads over abci_query
    def _account(self, priv) -> dict:
        import base64

        addr = priv.pub_key().address()
        res = self.client.call("abci_query", path="/account", data=addr.hex())
        raw = base64.b64decode(res["response"].get("value") or "")
        return json.loads(raw) if raw else {}

    def _committed_nonce(self, priv) -> int:
        return int(self._account(priv).get("nonce") or 0)

    def _balance(self, priv) -> int:
        return int(self._account(priv).get("balance") or 0)

    def _fund(self, treasury) -> None:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            t_nonce = self._committed_nonce(treasury)
            tx = self._make(treasury, self.priv.pub_key().address(),
                            self.FUNDING, t_nonce, self.chain_id)
            try:
                self.client.call("broadcast_tx_sync", tx=tx.hex())
            except Exception:
                time.sleep(0.5)
                continue
            # wait for the funding transfer to commit (or lose a nonce
            # race with a concurrent spigot and try again)
            settle = time.monotonic() + 20
            while time.monotonic() < settle:
                if self._balance(self.priv) >= self.FUNDING // 2:
                    return
                time.sleep(0.5)
        raise TimeoutError("bank spigot: worker funding never committed")

    def __call__(self) -> bytes:
        tx = self._make(self.priv, os.urandom(20), 1, self.nonce, self.chain_id)
        # tmcheck: ok[shared-mutation] each spigot instance is thread-confined: the load thread and every flood thread construct their OWN purpose-keyed spigot (see _tx_source); nonce never crosses threads
        self.nonce += 1
        return tx

    def rollback(self) -> None:
        """The caller could not submit the last tx: hand its nonce back."""
        # tmcheck: ok[shared-mutation] thread-confined (see __call__): one spigot per load/flood thread, never shared
        self.nonce -= 1

    def maybe_resync(self) -> None:
        """Self-heal a nonce desync. Two ways the local cursor drifts
        AHEAD of the chain for good: a kill/restart perturbation drops
        a mempool holding our in-flight txs (their nonces are gone
        forever), or a timed-out-but-accepted submission got its nonce
        handed back and re-spent. In-flight txs make local > committed
        NORMAL, so only reset when the committed nonce has not moved
        since the last probe while we sit ahead of it — a live drain
        always advances between probes (callers probe every few
        seconds), a dead chain gap never does."""
        try:
            c = self._committed_nonce(self.priv)
        except Exception:  # noqa: BLE001 - probe rides the load loop; RPC blips are its caller's problem
            return
        if c == self._last_committed and self.nonce > c:
            # tmcheck: ok[shared-mutation] thread-confined (see __call__)
            self.nonce = c
        self._last_committed = c


class Runner:
    """ref: test/e2e/runner/main.go Cleanup/Setup/Start/Load/Perturb/
    Wait/Test/Benchmark cycle."""

    def __init__(self, manifest: Manifest, base_dir: str, logger=print):
        self.manifest = manifest
        self.base_dir = base_dir
        self.log = logger
        self.nodes: list[E2ENode] = []
        self._load_proc_stop = False
        # packet-level fault plane (docs/faultnet.md): built in setup()
        # when the manifest asks for it; every persistent-peer link is
        # then carried through a per-link proxy named "dialer->target"
        self.faultnet = None
        self.faultnet_registry = None
        # tmlens verdict from the last analyze_artifacts() (cleanup
        # runs it); slow e2e tests assert on this after cleanup
        self.last_report: dict | None = None
        # live watch collector (start_watch): a daemon thread scrapes
        # every node's /metrics on a rolling cadence, keeps the last
        # scrape per node (persisted as metrics.last-watch.txt when a
        # node dies), and evaluates sliding-window gates
        # (lens/series.py RollingGates). First trip -> watch_tripped
        # is set, the wait loops raise WatchTripped, and the run
        # aborts with a full artifact sweep.
        self.watch_tripped: dict | None = None
        # extra environment for every spawned node/app process (merged
        # into _env); run_soak uses it for the small-box host-crypto pin
        self.extra_node_env: dict[str, str] = {}
        self._watch_thread = None
        self._watch_stop = None
        self._watch_hold = None
        self._watch_gates = None
        self._last_scrapes: dict[str, str] = {}

    # ----------------------------------------------------------------- setup

    def setup(self) -> None:
        """Validate the manifest, wipe any previous testnet at base_dir,
        then generate homes, keys, genesis, configs (ref: runner/main.go
        Cleanup before Setup — stale chain data from an earlier run
        would otherwise be resumed against a freshly generated genesis).
        Validation runs FIRST so a bad manifest never destroys the
        previous run's logs/WALs."""
        from .app import APP_NAMES

        ms = self.manifest.nodes
        if self.manifest.app not in APP_NAMES:
            raise ValueError(f"unknown app {self.manifest.app!r} (expected one of {APP_NAMES})")
        if self.manifest.genesis_accounts > 0 and self.manifest.app != "bank":
            raise ValueError(
                'genesis_accounts requires app = "bank" (only the bank '
                "app carries an account state plane)"
            )
        for nm in ms:
            if nm.state_sync and nm.start_at <= 0:
                raise ValueError(
                    f"{nm.name}: state_sync requires start_at > 0 (a late "
                    "joiner); a node started at genesis has nothing to restore"
                )
            if nm.state_sync and self.manifest.snapshot_interval <= 0:
                raise ValueError(
                    f"{nm.name}: state_sync requires manifest "
                    "snapshot_interval > 0 so some node produces snapshots"
                )
            if self.manifest.retain_blocks > 0 and nm.start_at > 0 and not nm.state_sync:
                raise ValueError(
                    f"{nm.name}: a blocksync-only late joiner cannot start "
                    "below a pruned provider's base (retain_blocks set)"
                )
            if nm.mode == "light" and nm.abci_protocol != "builtin":
                raise ValueError(f"{nm.name}: light proxies run no ABCI app")
            if nm.mode == "light" and nm.start_at > 0:
                # start() would launch it twice: once in the lights
                # wave (after the first block) and again as a late
                # joiner, the second Popen colliding on the same laddr
                raise ValueError(
                    f"{nm.name}: light proxies start after block 1, not at a height"
                )
        if any(nm.mode == "light" for nm in ms) and not any(
            nm.mode in ("validator", "full") and nm.start_at == 0 for nm in ms
        ):
            raise ValueError("light proxies need a genesis validator/full as primary")

        if os.path.isdir(self.base_dir):
            entries = os.listdir(self.base_dir)
            # a previous testnet is recognized by its layout (every
            # entry is a node home with config/, or a run artifact the
            # runner/analyzer itself writes into the base dir),
            # independent of THIS manifest's node names — refuse
            # anything else (protects against pointing the runner at
            # an unrelated directory)
            run_artifacts = {
                "fleet_report.json", "fleet_trace.json", "env_fingerprint.json",
            }
            looks_like_testnet = all(
                e in run_artifacts
                if os.path.isfile(os.path.join(self.base_dir, e))
                else os.path.isdir(os.path.join(self.base_dir, e, "config"))
                for e in entries
            )
            if entries and not looks_like_testnet:
                raise ValueError(
                    f"refusing to wipe {self.base_dir!r}: does not look "
                    "like a previous testnet (entries without config/ subdirs)"
                )
            import shutil

            shutil.rmtree(self.base_dir)
        if self.manifest.faultnet_needed:
            from ..metrics import FaultNetMetrics, Registry
            from ..faultnet import FaultNet

            self.faultnet_registry = Registry()
            self.faultnet = FaultNet(metrics=FaultNetMetrics(self.faultnet_registry))
            ambient = self.manifest.faultnet.policy_fields()
            if ambient:
                self.faultnet.set_default_policy(**ambient)
            self.log(f"faultnet enabled (ambient policy: {ambient or 'pass-through'})")
        ports = _free_ports(4 * len(ms))
        pvs = {}
        for i, nm in enumerate(ms):
            home = os.path.join(self.base_dir, nm.name)
            node = E2ENode(
                nm, home,
                ports[4 * i], ports[4 * i + 1], ports[4 * i + 2], ports[4 * i + 3],
            )
            os.makedirs(os.path.join(home, "config"), exist_ok=True)
            os.makedirs(os.path.join(home, "data"), exist_ok=True)
            if nm.mode == "light":
                # a light proxy is no consensus node: no keys, no
                # genesis, no p2p identity — it dials a primary's RPC
                # and serves the verifying proxy on its rpc_port (the
                # config/ dir exists only for the wipe guard's layout
                # recognition)
                self.nodes.append(node)
                continue
            cfg = default_config(home)
            pv = FilePV.load_or_generate(
                cfg.priv_validator_key_file, cfg.priv_validator_state_file,
                key_type=self.manifest.key_type,
            )
            node.node_id = NodeKey.load_or_gen(cfg.node_key_file).node_id
            if nm.mode == "validator":
                pvs[nm.name] = pv
            self.nodes.append(node)

        gen_doc = GenesisDoc(
            chain_id=self.manifest.chain_id,
            genesis_time=Time.now(),
            initial_height=self.manifest.initial_height,
            validators=[
                GenesisValidator(
                    address=pv.get_pub_key().address(), pub_key=pv.get_pub_key(), power=100, name=name
                )
                for name, pv in pvs.items()
            ],
        )
        # test-speed consensus timeouts — e2e runs measure fault recovery
        # and consistency, not production cadence (the reference's e2e
        # manifests shorten timeouts the same way)
        import dataclasses

        from ..types.params import (
            ABCIParams,
            ConsensusParams,
            TimeoutParams,
            ValidatorParams,
        )

        from ..types.params import BlockParams, EvidenceParams

        block_params = BlockParams()
        evidence_params = EvidenceParams()
        if self.manifest.block_max_bytes > 0:
            block_params = dataclasses.replace(
                block_params, max_bytes=self.manifest.block_max_bytes
            )
            # params validation demands evidence fits inside a block
            evidence_params = dataclasses.replace(
                evidence_params,
                max_bytes=min(evidence_params.max_bytes,
                              self.manifest.block_max_bytes // 3),
            )
        gen_doc.consensus_params = dataclasses.replace(
            ConsensusParams(),
            block=block_params,
            evidence=evidence_params,
            validator=ValidatorParams(pub_key_types=(self.manifest.key_type,)),
            abci=ABCIParams(
                vote_extensions_enable_height=self.manifest.vote_extensions_enable_height
            ),
            timeout=TimeoutParams(
                propose=600_000_000,
                propose_delta=200_000_000,
                vote=300_000_000,
                vote_delta=100_000_000,
                commit=100_000_000,
                bypass_commit_timeout=False,
            ),
        )

        for node in self.nodes:
            if node.m.mode == "light":
                continue
            cfg = default_config(node.home)
            gen_doc.save_as(cfg.genesis_file)
            cfg.base.moniker = node.m.name
            cfg.base.mode = node.m.mode
            cfg.p2p.laddr = f"tcp://127.0.0.1:{node.p2p_port}"
            cfg.rpc.laddr = f"tcp://127.0.0.1:{node.rpc_port}"
            # the runner drives partition fault injection over RPC
            cfg.rpc.unsafe = True
            # every node exports /metrics; the runner scrapes the final
            # exposition into the run dir at shutdown (observability
            # artifact — ref: the reference e2e's prometheus flag)
            cfg.instrumentation.prometheus = True
            cfg.instrumentation.prometheus_listen_addr = f"127.0.0.1:{node.prom_port}"
            # flight recorder ON in e2e (manifest default 1.0s): each
            # node streams delta records to <home>/timeseries.jsonl so
            # a SIGKILL'd node still leaves its rate timeline
            cfg.instrumentation.flight_interval = self.manifest.flight_interval
            if self.manifest.empty_blocks_interval > 0:
                cfg.consensus.create_empty_blocks_interval = (
                    self.manifest.empty_blocks_interval
                )
            cfg.p2p.send_rate = node.m.send_rate
            seeds = [o for o in self.nodes if o.m.mode == "seed"]
            if node.m.mode == "seed":
                # a seed dials nobody: it learns addresses from inbound
                # bootstrap dials and serves them over PEX (node/seed.go)
                cfg.p2p.persistent_peers = ""
            elif seeds:
                # seed-bootstrapped topology: nodes know ONLY the seeds;
                # PEX discovers the mesh (ref: manifest seeds + pex)
                cfg.p2p.bootstrap_peers = ",".join(
                    self._peer_addr(node, o) for o in seeds
                )
                cfg.p2p.persistent_peers = ""
            else:
                peers = [
                    self._peer_addr(node, o)
                    for o in self.nodes
                    if o is not node and o.m.mode != "light"
                ]
                cfg.p2p.persistent_peers = ",".join(peers)
            if self.faultnet is not None and not seeds:
                # Keep every byte inside the fault plane: without PEX
                # and with an undialable advertised address, a node can
                # only reach peers through its configured per-link
                # proxies — learned real addresses would bypass the
                # faults (seed topologies need PEX and keep it).
                cfg.p2p.pex = False
                cfg.p2p.external_address = "0.0.0.0:0"
            if node.m.abci_protocol in ("tcp", "unix", "grpc"):
                if node.m.abci_protocol == "unix":
                    addr = f"unix://{node.home}/app.sock"
                else:
                    addr = f"{node.m.abci_protocol}://127.0.0.1:{node.abci_port}"
                cfg.base.proxy_app = addr
            elif node.m.mode != "seed":
                spec = self._builtin_proxy_app()
                if spec is not None:
                    cfg.base.proxy_app = spec
            cfg.save()

        # tmperf environment fingerprint, persisted AT RUN TIME: the
        # fleet report's post-mortem reader (possibly on another box)
        # must be able to tell a slow box from a slow build — the
        # BENCH_r02/r03 CPU-emulation fallback would have been one
        # device-kind line here, not an XLA error-tail excavation.
        try:
            from ..perf.record import fingerprint

            with open(os.path.join(self.base_dir, "env_fingerprint.json"), "w") as f:
                json.dump(fingerprint(), f, indent=1)
        except Exception as e:  # noqa: BLE001 - telemetry must not sink setup
            self.log(f"env fingerprint failed: {type(e).__name__}: {e}")

    def _builtin_proxy_app(self) -> str | None:
        """builtin:<app>[:snapshot=N][:retain=M][:accounts=K] for the
        manifest's app axes, or None when the default config's plain
        kvstore already matches (node.py _make_app parses the same
        syntax)."""
        m = self.manifest
        if m.app == "kvstore" and m.snapshot_interval <= 0 and m.retain_blocks <= 0:
            return None
        spec = f"builtin:{m.app}"
        if m.snapshot_interval > 0:
            spec += f":snapshot={m.snapshot_interval}"
        if m.retain_blocks > 0:
            spec += f":retain={m.retain_blocks}"
        if m.genesis_accounts > 0:
            spec += f":accounts={m.genesis_accounts}"
        return spec

    def _peer_addr(self, dialer: E2ENode, target: E2ENode) -> str:
        """target's address as `dialer` should dial it: direct, or via a
        per-link faultnet proxy named 'dialer->target'."""
        if self.faultnet is None:
            return f"{target.node_id}@127.0.0.1:{target.p2p_port}"
        name = f"{dialer.m.name}->{target.m.name}"
        try:
            link = self.faultnet.link(name)
        except KeyError:
            link = self.faultnet.add_link(name, ("127.0.0.1", target.p2p_port))
        return f"{target.node_id}@{link.host}:{link.port}"

    def _configure_statesync(self, node: E2ENode) -> None:
        """Point a late joiner at a live node's RPC for the light-client
        trust root so it restores an app snapshot instead of replaying
        from genesis (ref: runner/setup.go state-sync config)."""
        candidates = [
            n for n in self._rpc_nodes() if n is not node and n.height() > 0
        ]
        if not candidates:
            raise RuntimeError(f"{node.m.name}: no live statesync trust source")
        # the trust root must come from an HONEST node: chunk traffic is
        # p2p (a statesync_corrupt provider gets rotated away by the
        # joiner's own hardening, which is the point of the byz run),
        # but a poisoned trust HASH would wedge the restore before the
        # hardening ever gets a say
        source = next((n for n in candidates if not n.m.byzantine), candidates[0])
        # trust root: the source's CURRENT HEAD. Genesis is the obvious
        # choice but a retain_blocks provider prunes it away — and any
        # fixed low height races the advancing prune window between
        # config time and the joiner's first light-block fetch (seen
        # live: configured earliest=3, fetch-time lowest=5). The head
        # can never be pruned out from under the join, and the light
        # client hash-chain-walks BACKWARD from it to the snapshot
        # height (light/client.py _verify_backwards).
        status = source.client().call("status")
        trust_h = max(
            self.manifest.initial_height,
            int(status["sync_info"]["latest_block_height"]),
        )
        trust = source.client().call("commit", height=trust_h)
        cfg = load_config(node.home)
        cfg.statesync.enable = True
        cfg.statesync.rpc_servers = source.rpc_url
        cfg.statesync.trust_height = trust_h
        cfg.statesync.trust_hash = trust["signed_header"]["commit"]["block_id"]["hash"]
        cfg.save()

    def _rpc_nodes(self, nodes=None) -> list:
        """Consensus-participating, RPC-serving nodes — seeds run the
        pex-only SeedNode with no RPC listener, and light proxies serve
        a VERIFYING facade whose head trails its primary (asserted
        separately, never part of consensus waits)."""
        return [n for n in (nodes or self.nodes) if n.m.mode not in ("seed", "light")]

    # ----------------------------------------------------------------- start

    def _env(self) -> dict:
        env = dict(os.environ)
        # A chip belongs to one process, and a testnet is many: node
        # subprocesses stay on the CPU backend and never claim it.
        env["JAX_PLATFORMS"] = "cpu"
        root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
        # per-run node knobs (run_soak's small-box host-crypto pin rides
        # here); explicit operator env still wins over the defaults we
        # inject because extra entries are merged, not forced
        env.update(self.extra_node_env)
        return env

    def _delays_env(self) -> str:
        """JSON ABCI-delay schedule for app processes, '' when unset.
        Negative manifest values are rejected up front — a bad sleep
        would otherwise crash the app subprocess with stderr discarded."""
        delays = {
            "prepare_proposal": self.manifest.prepare_proposal_delay_ms,
            "process_proposal": self.manifest.process_proposal_delay_ms,
            "check_tx": self.manifest.check_tx_delay_ms,
            "finalize_block": self.manifest.finalize_block_delay_ms,
        }
        if any(v < 0 for v in delays.values()):
            raise ValueError(f"negative ABCI delay in manifest: {delays}")
        return json.dumps(delays) if any(delays.values()) else ""

    def _start_node(self, node: E2ENode) -> None:
        if node.m.mode == "light":
            self._start_light_node(node)
            return
        if node.m.abci_protocol in ("tcp", "unix", "grpc"):
            cfg = load_config(node.home)
            app_env = self._env()
            if self._delays_env():
                app_env["TM_E2E_DELAYS_MS"] = self._delays_env()
            node.app_proc = subprocess.Popen(
                [sys.executable, "-m", "tendermint_tpu.e2e.app", cfg.base.proxy_app,
                 str(self.manifest.snapshot_interval), self.manifest.app,
                 str(self.manifest.retain_blocks), node.home,
                 str(self.manifest.genesis_accounts)],
                env=app_env,
                stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL,
            )
            # the app process imports jax (seconds); the node dials the
            # app in its constructor, so wait until the socket accepts
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                try:
                    if node.m.abci_protocol in ("tcp", "grpc"):
                        socket.create_connection(("127.0.0.1", node.abci_port), timeout=1).close()
                    else:
                        s = socket.socket(socket.AF_UNIX)
                        s.connect(f"{node.home}/app.sock")
                        s.close()
                    break
                except OSError:
                    time.sleep(0.2)
            else:
                raise TimeoutError(f"{node.m.name}: ABCI app never came up")
        log_f = open(os.path.join(node.home, "node.log"), "ab")
        node_env = self._env()
        if node.m.byzantine:
            # arms tendermint_tpu.byz.maybe_install inside cmd_start,
            # before the node binds the classes the roles monkeypatch
            node_env["TM_TPU_BYZ"] = node.m.byzantine
        if node.m.abci_protocol == "builtin" and self._delays_env():
            # builtin apps are constructed inside the node process
            # (node/node.py _make_app) — same env contract as the
            # external app runner
            node_env["TM_E2E_DELAYS_MS"] = self._delays_env()
        node.proc = subprocess.Popen(
            [sys.executable, "-m", "tendermint_tpu", "--home", node.home, "start"],
            env=node_env,
            stdout=log_f,
            stderr=subprocess.STDOUT,
        )
        log_f.close()

    def _start_light_node(self, node: E2ENode) -> None:
        """Spawn the verifying light proxy (`tendermint_tpu light`)
        against the first live consensus node; its rpc_port serves the
        proxied, light-verified RPC surface."""
        live = [n for n in self._rpc_nodes() if n is not node and n.height() > 0]
        # a header-forging adversary is the PREFERRED primary: the whole
        # point of running a light proxy next to one is watching the
        # proxy refuse its forged light_batch headers and log them into
        # the divergence report
        primary = next(
            (n for n in live if "header_forge" in n.m.byzantine),
            live[0] if live else None,
        )
        if primary is None:
            raise RuntimeError(f"{node.m.name}: no live primary for the light proxy")
        log_f = open(os.path.join(node.home, "light.log"), "ab")
        node.proc = subprocess.Popen(
            [sys.executable, "-m", "tendermint_tpu", "light",
             self.manifest.chain_id, primary.rpc_url,
             "--laddr", f"tcp://127.0.0.1:{node.rpc_port}",
             "--interval", "1.0",
             "--report", os.path.join(node.home, "light_divergence.json")],
            env=self._env(),
            stdout=log_f,
            stderr=subprocess.STDOUT,
        )
        log_f.close()

    def start(self, timeout: float = 120.0, defer: set[str] | None = None) -> None:
        """Start nodes in waves like the reference (runner/start.go):
        all start_at=0 first, stragglers once the net is past their
        start height. Light proxies start after the first block exists
        (their trust root is the primary's current head). Nodes named
        in `defer` are left unstarted — a soak timeline's
        statesync_join events own them (Runner.soak)."""
        defer = defer or set()
        initial = [n for n in self.nodes
                   if n.m.start_at == 0 and n.m.mode != "light"]
        late = [n for n in self.nodes
                if n.m.start_at > 0 and n.m.name not in defer]
        lights = [n for n in self.nodes if n.m.mode == "light"]
        for node in initial:
            self._start_node(node)
        self.wait_ready(initial, timeout=timeout)
        if lights:
            self.wait_for_height(1, nodes=initial, timeout=timeout)
            for node in lights:
                self._start_node(node)
        for node in sorted(late, key=lambda n: n.m.start_at):
            self.wait_for_height(node.m.start_at, nodes=initial, timeout=timeout)
            if node.m.state_sync:
                self._configure_statesync(node)
            self._start_node(node)
        started = len(self.nodes) - len(defer)
        self.log(f"started {started} node processes"
                 + (f" ({len(defer)} deferred to the timeline)" if defer else ""))

    def wait_ready(self, nodes=None, timeout: float = 120.0) -> None:
        deadline = time.monotonic() + timeout
        pending = self._rpc_nodes(nodes)
        while pending and time.monotonic() < deadline:
            self.check_watch()
            pending = [n for n in pending if n.height() < 0]
            time.sleep(0.2)
        if pending:
            raise TimeoutError(f"nodes never became ready: {[n.m.name for n in pending]}")

    # ----------------------------------------------------------------- watch

    def start_watch(self, interval: float = 2.0, gates: dict | None = None) -> None:
        """Start the live collector thread (lens/series.py
        RollingGates over every node's /metrics). Gate keys:
        WATCH_DEFAULTS; a trip aborts the run at the next wait loop
        (check_watch) instead of timing out minutes later."""
        import threading

        from ..lens.series import RollingGates

        if self._watch_thread is not None:
            return
        self._watch_gates = RollingGates(gates)
        self._watch_stop = threading.Event()
        self._watch_hold = threading.Event()
        self._watch_interval = interval
        self._watch_thread = threading.Thread(
            target=self._watch_loop, daemon=True, name="e2e-watch"
        )
        self._watch_thread.start()
        self.log(f"live watch started ({interval}s cadence)")

    def stop_watch(self) -> None:
        if self._watch_stop is not None:
            self._watch_stop.set()
        t = self._watch_thread
        if t is not None:
            t.join(timeout=5)
            self._watch_thread = None

    def hold_watch(self) -> None:
        """Suspend gate EVALUATION (scraping continues, so last-watch
        snapshots stay fresh) around intentional perturbations — a
        deliberately partitioned node must not trip the stall gate."""
        if self._watch_hold is not None:
            self._watch_hold.set()

    def resume_watch(self) -> None:
        if self._watch_hold is not None and self._watch_hold.is_set():
            if self._watch_gates is not None:
                # windows carry pre-perturbation progress clocks;
                # judging recovery against them would false-trip.
                # Reset BEFORE releasing the hold: while held the watch
                # thread never enters evaluate(), so clearing the node
                # map here cannot race its dict iteration.
                self._watch_gates.reset()
            self._watch_hold.clear()

    def check_watch(self) -> None:
        """Raise WatchTripped if the collector tripped a gate — called
        from every wait loop so the run aborts within one poll tick."""
        if self.watch_tripped is not None:
            raise WatchTripped(self.watch_tripped["gate"], self.watch_tripped["detail"])

    def _watch_loop(self) -> None:
        from ..lens.series import scrape_metrics

        while not self._watch_stop.wait(self._watch_interval):
            now = time.time()
            for node in self.nodes:
                if node.m.mode in ("seed", "light") or not node.prom_port:
                    continue
                if node.proc is None or node.proc.poll() is not None:
                    continue  # dead: its last scrape is already held
                try:
                    body, exp = scrape_metrics(
                        f"http://127.0.0.1:{node.prom_port}/metrics", timeout=2.0
                    )
                except Exception:  # noqa: BLE001 - scrape gaps are data, not faults
                    continue
                self._last_scrapes[node.m.name] = body
                try:
                    self._watch_gates.observe(node.m.name, exp, t=now)
                except Exception as e:  # noqa: BLE001
                    self.log(f"watch observe failed for {node.m.name}: {e}")
            if self._watch_hold is not None and self._watch_hold.is_set():
                continue
            if self._watch_stop.is_set():
                # stop_watch() fired mid-sweep (a sweep can take seconds
                # against unresponsive nodes and outlive the 5s join):
                # a teardown-time "trip" would flip a passing run's
                # verdict and race cleanup's own artifact sweep
                return
            try:
                tripped = self._watch_gates.evaluate(now=time.time())
            except Exception as e:  # noqa: BLE001 - the watch must outlive bugs
                self.log(f"watch evaluate failed: {type(e).__name__}: {e}")
                continue
            if tripped:
                g = tripped[0]
                self.watch_tripped = {
                    "gate": g["name"],
                    "detail": g["detail"],
                    "t": time.time(),
                    "all": tripped,
                }
                self.log(f"WATCH TRIPPED: {g['name']} — {g['detail']}")
                # sweep NOW: the state at trip time is the evidence
                # (cleanup's final sweep still runs later)
                try:
                    self.collect_artifacts(suffix=".on-trip")
                except Exception as e:  # noqa: BLE001 - evidence only
                    self.log(f"on-trip artifact sweep failed: {e}")
                return

    def _persist_last_watch(self, node: E2ENode) -> None:
        """Persist the collector's most recent scrape of this node as
        metrics.last-watch.txt — the freshest telemetry a node that is
        about to be (or already was) SIGKILL'd can leave, alongside the
        perturb() pre-kill snapshot (which covers runner-initiated
        kills only)."""
        body = self._last_scrapes.get(node.m.name)
        if not body:
            return
        try:
            with open(os.path.join(node.home, "metrics.last-watch.txt"), "w") as f:
                f.write(body)
        except OSError as e:
            self.log(f"last-watch persist failed for {node.m.name}: {e}")

    # ------------------------------------------------------------------ load

    def _tx_source(self, label: str):
        """next_tx() -> bytes for this manifest's app: self-describing
        k=v txs for the kvstore, signed worker-account transfers for
        the bank (each a REAL state transition growing the account
        set). Bank sources expose rollback() — a failed submission
        hands its nonce back — and bank submissions are PINNED to one
        RPC node so the per-sender nonce chain is admitted in order."""
        if self.manifest.app == "bank":
            return _BankSpigot(self.manifest.chain_id,
                               self._rpc_nodes_started()[0].client(),
                               purpose=label)
        counter = iter(range(1, 1 << 31))

        def next_tx() -> bytes:
            i = next(counter)
            return f"{label}-{os.getpid()}-{i}={i}".encode()

        return next_tx

    def _load_targets(self):
        """Submission targets: every STARTED RPC node (a soak-deferred
        late joiner has no process to refuse the connection), or just
        the first for the bank's sequenced-nonce load (see
        _tx_source)."""
        targets = self._rpc_nodes_started()
        return targets[:1] if self.manifest.app == "bank" else targets

    def inject_load(self, duration: float) -> int:
        """Round-robin app txs at manifest.load_tx_rate
        (ref: runner/load.go)."""
        rate = max(1, self.manifest.load_tx_rate)
        interval = 1.0 / rate
        sent = 0
        deadline = time.monotonic() + duration
        i = 0
        targets = self._load_targets()
        next_tx = self._tx_source("load")
        next_resync = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if hasattr(next_tx, "maybe_resync") and time.monotonic() >= next_resync:
                next_tx.maybe_resync()
                next_resync = time.monotonic() + 5.0
            node = targets[i % len(targets)]
            i += 1
            try:
                tx = next_tx()
                res = node.client().call("broadcast_tx_async", tx=tx.hex())
                # a queue-full rejection comes back as a nonzero code,
                # not an exception — it must hand the nonce back too,
                # or one saturated admission queue poisons every later
                # bank transfer with BAD_NONCE
                if int(res.get("code", 0)) == 0:
                    sent += 1
                elif hasattr(next_tx, "rollback"):
                    next_tx.rollback()
            except Exception:
                if hasattr(next_tx, "rollback"):
                    next_tx.rollback()
            time.sleep(interval)
        return sent

    def inject_flood(
        self, n_txs: int = 0, batch: int = 200, timeout: float = 300.0,
        label: str = "flood",
    ) -> list[bytes]:
        """Burst-flood app txs through broadcast_tx_async — the
        bounded admission queue draining into check_tx_batch — as fast
        as the RPC accepts them, round-robin across nodes (vs
        inject_load's paced one-tx-per-interval drip). Backpressure
        (code 1, admission queue full) retries the tx after a short
        pause instead of dropping it; the deadline bounds the whole
        flood so dead RPC endpoints fail the run loudly instead of
        hanging it. Returns the tx bytes submitted."""
        n_txs = n_txs or self.manifest.flood_txs
        targets = self._load_targets()
        sent: list[bytes] = []
        i = 0
        deadline = time.monotonic() + timeout
        next_tx = self._tx_source(label)
        while len(sent) < n_txs:
            self.check_watch()
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"flood stalled: {len(sent)}/{n_txs} txs submitted in {timeout}s"
                )
            node = targets[i % len(targets)]
            i += 1
            for _ in range(batch):
                if len(sent) >= n_txs:
                    break
                tx = next_tx()
                try:
                    res = node.client().call("broadcast_tx_async", tx=tx.hex())
                except Exception:
                    if hasattr(next_tx, "rollback"):
                        next_tx.rollback()
                    time.sleep(0.1)
                    continue
                if int(res.get("code", 0)) == 0:
                    sent.append(tx)
                else:
                    if hasattr(next_tx, "rollback"):
                        next_tx.rollback()
                    time.sleep(0.05)  # queue full: let the worker drain
        self.log(f"flooded {len(sent)} txs via broadcast_tx_async")
        return sent

    def apply_validator_updates(self, timeout: float = 90.0) -> None:
        """Apply the manifest's validator_update schedule: at each
        listed height, submit the kvstore's val-change tx for the named
        node's pubkey and wait until the chain's validator set reports
        the new power (ref: manifest.go ValidatorUpdates +
        runner/main.go applying them via the app)."""
        if not self.manifest.validator_updates:
            return
        from ..abci.kvstore import make_validator_tx

        client = self._rpc_nodes()[0].client()
        by_name = {n.m.name: n for n in self.nodes}
        for h in sorted(self.manifest.validator_updates):
            updates = self.manifest.validator_updates[h]
            self.wait_for_height(h, timeout=timeout)
            want = {}
            for name, power in updates.items():
                cfg = load_config(by_name[name].home)
                pv = FilePV.load(cfg.priv_validator_key_file, cfg.priv_validator_state_file)
                pub = pv.get_pub_key()
                tx = make_validator_tx(pub.bytes(), power, key_type=pub.type_name)
                res = client.call("broadcast_tx_sync", tx=tx.hex())
                if int(res.get("code", 0)) != 0:
                    raise RuntimeError(
                        f"validator-update tx rejected: {res.get('log')!r}"
                    )
                want[pub.address().hex().upper()] = power
                self.log(f"validator update @ {h}: {name} -> power {power}")
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                try:
                    res = client.call("validators")
                    got = {v["address"]: int(v["voting_power"]) for v in res["validators"]}
                    if all(
                        (got.get(a) == p if p > 0 else a not in got)
                        for a, p in want.items()
                    ):
                        break
                except Exception:
                    pass
                time.sleep(0.25)
            else:
                raise TimeoutError(f"validator updates at height {h} never took effect: {want}")

    def inject_evidence(self, timeout: float = 60.0) -> str:
        """Craft real duplicate-vote evidence — two conflicting
        precommits at a committed height signed with a testnet
        validator's own key — submit it via broadcast_evidence, and wait
        for it to be committed into a block (ref:
        test/e2e/runner/evidence.go InjectEvidence). Returns the
        evidence hash hex."""
        from ..proto.messages import SIGNED_MSG_TYPE_PRECOMMIT
        from ..types.block import BlockID, PartSetHeader
        from ..types.evidence import DuplicateVoteEvidence
        from ..types.validator_set import Validator, ValidatorSet
        from ..types.vote import Vote

        offender = next(n for n in self.nodes if n.m.mode == "validator")
        cfg = load_config(offender.home)
        pv = FilePV.load(cfg.priv_validator_key_file, cfg.priv_validator_state_file)
        priv = pv.priv_key
        addr = priv.pub_key().address()
        gen_doc = GenesisDoc.from_file(cfg.genesis_file)
        # canonical (sorted) construction — must match make_genesis_state
        # so validator_index lines up with the chain's real set
        val_set = ValidatorSet.new(
            [Validator(address=v.address, pub_key=v.pub_key, voting_power=v.power)
             for v in gen_doc.validators]
        )
        val_idx, _ = val_set.get_by_address(addr)

        live = next(n for n in self._rpc_nodes() if n is not offender)
        client = live.client()
        status = client.call("status")
        h = int(status["sync_info"]["latest_block_height"]) - 1
        if h < self.manifest.initial_height:
            raise RuntimeError("chain too short to inject evidence")
        blk = client.call("block", height=h)
        block_time = Time.parse_rfc3339(blk["block"]["header"]["time"])

        def vote(tag: bytes) -> Vote:
            v = Vote(
                type=SIGNED_MSG_TYPE_PRECOMMIT,
                height=h,
                round=0,
                block_id=BlockID(hash=tag * 32,
                                 part_set_header=PartSetHeader(total=1, hash=tag * 32)),
                timestamp=block_time,
                validator_address=addr,
                validator_index=val_idx,
            )
            v.signature = priv.sign(v.sign_bytes(gen_doc.chain_id))
            return v

        ev = DuplicateVoteEvidence.new(vote(b"\xaa"), vote(b"\xbb"), block_time, val_set)
        from ..types.evidence import evidence_to_proto

        res = client.call("broadcast_evidence",
                          evidence=evidence_to_proto(ev).encode().hex())
        # block JSON carries the BARE evidence proto (block_to_json),
        # not the Evidence oneof wrapper the RPC ingests
        ev_hex = ev.to_proto().encode().hex()
        ev_hash = res["hash"]
        self.log(f"injected duplicate-vote evidence {ev_hash} at height {h}")

        # wait until a block commits THIS evidence (tx load and
        # perturbations run concurrently: transient RPC failures retry
        # within the deadline)
        deadline = time.monotonic() + timeout
        scanned = h
        while time.monotonic() < deadline:
            try:
                head = live.height()
                for look in range(scanned + 1, head + 1):
                    b = client.call("block", height=look)
                    if ev_hex in b["block"]["evidence"]["evidence"]:
                        return ev_hash
                    scanned = look
            except Exception:
                pass
            time.sleep(0.25)
        raise TimeoutError("evidence was never committed to a block")

    # ---------------------------------------------------------------- perturb

    def perturb(self, node: E2ENode, kind: str) -> None:
        """ref: runner/perturb.go:40-72 (disconnect/kill/pause/restart)."""
        self.log(f"perturb {node.m.name}: {kind}")
        if kind in ("kill", "restart"):
            # The dying process takes its in-memory trace ring and
            # /metrics state with it; snapshot them FIRST (suffixed so
            # the final collection doesn't overwrite the evidence) —
            # a run that aborts after this perturbation still leaves
            # the victim's pre-death state for tmlens.
            try:
                self.collect_artifacts(nodes=[node], suffix=f".pre-{kind}")
            except Exception as e:  # noqa: BLE001 - evidence only
                self.log(f"pre-{kind} artifact snapshot failed for {node.m.name}: {e}")
            # the collector's cadence scrape too: its timestamp dates
            # the telemetry independently of this perturb call
            self._persist_last_watch(node)
        if kind == "kill":
            # node AND its out-of-process app are one failure domain —
            # the reference's kill is `docker kill` of the container
            # holding both (perturb.go:52; the e2e binary embeds the
            # app). Leaving the app alive hands the restarted node an
            # app whose in-memory height includes an uncommitted
            # FinalizeBlock, an unreachable state in the reference.
            node.proc.send_signal(signal.SIGKILL)
            if node.app_proc is not None:
                node.app_proc.send_signal(signal.SIGKILL)
                node.app_proc.wait(timeout=10)
            node.proc.wait(timeout=10)
            self._start_node(node)
        elif kind == "restart":
            node.proc.send_signal(signal.SIGTERM)
            try:
                node.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                node.proc.kill()
                node.proc.wait(timeout=10)
            if node.app_proc is not None:
                node.app_proc.send_signal(signal.SIGTERM)
                try:
                    node.app_proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    node.app_proc.kill()
                    node.app_proc.wait(timeout=10)
            self._start_node(node)
        elif kind == "pause":
            node.proc.send_signal(signal.SIGSTOP)
            time.sleep(5.0)
            node.proc.send_signal(signal.SIGCONT)
        elif kind == "disconnect":
            # a REAL partition (ref: perturb.go:43 docker network
            # disconnect): SIGUSR1 makes the node's router close every
            # p2p connection and refuse new ones — peers see immediate
            # EOF/reset (not a silent stall as under SIGSTOP) — then
            # SIGUSR2 reconnects and the node must re-dial and recover
            node.proc.send_signal(signal.SIGUSR1)
            time.sleep(8.0)
            node.proc.send_signal(signal.SIGUSR2)
        elif kind == "partition":
            # transport-level ASYMMETRIC partition (VERDICT r4 item 7):
            # the node vetoes every peer over unsafe RPC — connections
            # close NOW and are refused per-link while the rest of the
            # net keeps committing; the vetoed majority exercises real
            # dial-failure/backoff paths against a live listener. The
            # partitioned minority must stall (no quorum reachable),
            # then heal and catch up.
            client = node.client()
            others = [o.node_id for o in self.nodes if o is not node and o.node_id]
            height_before = int(
                client.call("status")["sync_info"]["latest_block_height"]
            )
            client.call("unsafe_partition", peers=others)
            live = [
                o for o in self.nodes
                if o is not node and o.m.mode == "validator"
            ]
            if live:
                # majority keeps committing while the minority is cut off
                target = self._max_height(live) + 2
                self._wait_heights(live, target, timeout=60)
            time.sleep(2.0)
            stalled = int(client.call("status")["sync_info"]["latest_block_height"])
            if stalled > height_before + 1:
                raise AssertionError(
                    f"{node.m.name} kept committing while partitioned "
                    f"({height_before} -> {stalled})"
                )
            client.call("unsafe_heal")
            # run_perturbations' wait_progress gates the NEXT
            # perturbation on this node's height advancing — which a
            # lone partitioned validator cannot do without reconnecting
            # and catching up, so heal-then-repartition starvation
            # can't sneak past it.
        elif kind == "blackhole":
            # packet-level severance BELOW the router (docs/faultnet.md):
            # every link touching this node goes black in both
            # directions, and live proxied connections are RST so
            # re-dials become mid-handshake black holes — the dialer's
            # TCP connect succeeds, its handshake bytes vanish, and the
            # handshake watchdog must fail it over within its timeout.
            # The rest of the net must keep committing throughout.
            fn = self.faultnet
            assert fn is not None, "blackhole perturbation without faultnet"
            fn.fault_node(node.m.name, blackhole=True, drop_conns=True)
            live = [
                o for o in self.nodes
                if o is not node and o.m.mode == "validator"
            ]
            if live:
                target = self._max_height(live) + 2
                self._wait_heights(live, target, timeout=90)
            fn.heal_node(node.m.name)
            # wait_progress (run_perturbations) asserts the victim
            # recovers through the healed links
        elif kind == "halfopen":
            # one of the node's links freezes: the proxy stops reading,
            # so the peer stays TCP-ESTABLISHED while every byte the
            # node sends backs up into kernel buffers. The node must NOT
            # stall — consensus continues over its other links and the
            # MConn pong timeout eventually reaps the dead one.
            fn = self.faultnet
            assert fn is not None, "halfopen perturbation without faultnet"
            links = [
                l for l in fn.node_links(node.m.name)
                if l.name.startswith(f"{node.m.name}->")
            ]
            assert links, f"{node.m.name} has no outbound faultnet links"
            victim_link = links[0]
            fn.fault(victim_link.name, half_open=True)
            live = [
                o for o in self.nodes
                if o is not node and o.m.mode == "validator"
            ]
            if live:
                # block production sustained with the frozen link in place
                target = self._max_height(live) + 2
                self._wait_heights(live, target, timeout=90)
            # the faulted node itself must also keep advancing: a single
            # half-open peer out of n-1 must never stall it
            self.wait_progress(node, timeout=90)
            victim_link.heal()
            victim_link.drop_connections()  # unblock writers wedged in the freeze
        else:
            raise ValueError(f"unknown perturbation {kind!r}")

    def _max_height(self, nodes) -> int:
        best = 0
        for o in nodes:
            try:
                c = o.client()
                best = max(best, int(c.call("status")["sync_info"]["latest_block_height"]))
            except Exception:
                continue
        return best

    def _wait_heights(self, nodes, target: int, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self.check_watch()
            if self._max_height(nodes) >= target:
                return
            time.sleep(0.25)
        raise TimeoutError(f"majority never reached height {target} during partition")

    def run_perturbations(self) -> None:
        # gate evaluation pauses for the whole perturbation phase: a
        # deliberately partitioned/blackholed node IS stalled, and its
        # recovery is judged by wait_progress's own timeout. Scraping
        # continues so metrics.last-watch.txt stays fresh.
        self.hold_watch()
        try:
            for node in self.nodes:
                for kind in node.m.perturb:
                    self.perturb(node, kind)
                    if node.m.mode in ("seed", "light"):
                        # seeds serve no RPC (and a light proxy's head is
                        # its primary's): "recovered" = the (possibly
                        # freshly restarted) process stays alive for a grace
                        # period
                        time.sleep(2)
                        assert node.proc is not None and node.proc.poll() is None, (
                            f"{node.m.name} did not survive {kind}"
                        )
                    else:
                        self.wait_progress(node, timeout=90)
                        # progress alone is not recovery any more: the
                        # native AEAD plane dropped idle block time to
                        # ~0.2s, so a restarted node that advanced one
                        # height can still trail the sprinting chain by
                        # more than the live height_spread budget the
                        # moment evaluation resumes (seen live on
                        # ci-live) — hold until it is back in reach
                        self._wait_caught_up(node, timeout=90)
        finally:
            self.resume_watch()

    # ------------------------------------------------------------------ soak

    def soak(self, duration: float, timeline=None, load: bool = True,
             perturb_timeout: float = 90.0, watch_gates: dict | None = None) -> dict:
        """Drive the manifest's scenario timeline under the live watch
        plane (ISSUE 14): start the rolling gates, keep a paced tx load
        running for `duration`, and walk the resolved timeline on a
        wall clock — rolling restarts and kill/pause storms with the
        watch HELD around each intentional fault (the run_perturbations
        discipline), floods launched in the background so a
        statesync_join event really lands mid-flood. Ends by waiting
        for every node (late joiners included) to converge and
        checking block-hash consistency. Caller owns setup()/start()/
        cleanup(); nodes named in statesync_join events must have been
        deferred at start (run_soak wires this)."""
        import threading

        from .scenario import SoakTimeline

        tl = timeline if timeline is not None else SoakTimeline.from_manifest(self.manifest)
        actions = tl.resolve(self.manifest)
        self.start_watch(gates=watch_gates)
        # deferred statesync_join nodes are not running yet: every wait
        # until the convergence phase judges only STARTED nodes. The
        # initial wait never judges tighter than the caller's declared
        # live stall tolerance: a run that legitimately pauses at start
        # (first XLA compile/cache-load with the device crypto plane
        # forced on) widens stall_after_s, and this wait must not abort
        # what the watch was told to allow.
        self.wait_for_height(
            2, nodes=self._rpc_nodes_started(),
            timeout=max(120.0, float((watch_gates or {}).get("stall_after_s", 0.0))),
        )
        load_thread = None
        if load and self.manifest.load_tx_rate > 0:
            load_thread = threading.Thread(
                target=self.inject_load, args=(duration,), daemon=True, name="soak-load"
            )
            load_thread.start()
        floods: list = []  # per-flood submitted counts (threads append)
        flood_threads: list[threading.Thread] = []
        by_name = {n.m.name: n for n in self.nodes}
        t0 = time.monotonic()
        for act in actions:
            while time.monotonic() - t0 < act["at"]:
                self.check_watch()
                time.sleep(0.2)
            kind = act["kind"]
            self.log(f"soak t={act['at']:g}s: {kind} {','.join(act['nodes'])}")
            if kind == "flood":
                # purpose-keyed per event: two floods in one timeline
                # run concurrently, and sharing one deterministic
                # worker account would race its nonce chain
                def _flood(n=act["txs"], lbl=f"flood@{act['at']:g}"):
                    try:
                        floods.append(len(self.inject_flood(n_txs=n, label=lbl)))
                    except Exception as e:  # noqa: BLE001 - watch judges health
                        self.log(f"soak flood errored: {type(e).__name__}: {e}")

                th = threading.Thread(target=_flood, daemon=True, name="soak-flood")
                th.start()
                flood_threads.append(th)
            elif kind == "statesync_join":
                # a joining node legitimately trails the fleet until its
                # restore + catch-up completes: hold gate EVALUATION for
                # the join window (the run_perturbations discipline —
                # scraping continues) or the live height_spread gate
                # aborts an intentional scenario (seen live)
                self.hold_watch()
                try:
                    for name in act["nodes"]:
                        node = by_name[name]
                        if node.proc is not None:
                            continue  # start() already launched it (not deferred)
                        self.wait_for_height(
                            node.m.start_at, nodes=self._rpc_nodes_started(),
                        )
                        if node.m.state_sync:
                            self._configure_statesync(node)
                        self._start_node(node)
                        # caught up = within live height_spread reach of
                        # the CURRENT fleet head, not the head at join
                        # time: the chain keeps committing through the
                        # restore, and resuming the watch against a
                        # stale target left the joiner 16 heights back
                        # the moment evaluation resumed (seen live
                        # under sanitizer load)
                        self._wait_caught_up(
                            node, timeout=max(120.0, perturb_timeout + 60.0)
                        )
                finally:
                    self.resume_watch()
            elif kind in ("rolling_restart", "churn"):
                one_kind = "restart" if kind == "rolling_restart" else "disconnect"
                self.hold_watch()
                try:
                    for name in act["nodes"]:
                        self.perturb(by_name[name], one_kind)
                        self.wait_progress(by_name[name], timeout=perturb_timeout)
                        self._wait_caught_up(by_name[name], timeout=perturb_timeout)
                        time.sleep(act.get("gap", 1.0))
                finally:
                    self.resume_watch()
            else:  # kill | pause | restart | disconnect | partition | blackhole | halfopen
                self.hold_watch()
                try:
                    for name in act["nodes"]:
                        node = by_name[name]
                        self.perturb(node, kind)
                        if node.m.mode in ("seed", "light"):
                            time.sleep(2)
                            assert node.proc is not None and node.proc.poll() is None, (
                                f"{name} did not survive {kind}"
                            )
                        else:
                            self.wait_progress(node, timeout=perturb_timeout)
                            self._wait_caught_up(node, timeout=perturb_timeout)
                finally:
                    self.resume_watch()
        if load_thread is not None:
            remaining = duration - (time.monotonic() - t0)
            load_thread.join(timeout=max(0.0, remaining) + 60)
        for th in flood_threads:
            th.join(timeout=120)
        # convergence: every STARTED consensus node (timeline late
        # joiners included — their join events have fired by now; a
        # timeline that never joined a deferred node leaves it out)
        h = self._max_height(self._rpc_nodes_started())
        self.wait_for_height(h + 2, nodes=self._rpc_nodes_started())
        self.check_consistency()
        return {
            "actions": actions,
            "flood_submitted": sum(floods),
            "height": self._max_height(self._rpc_nodes()),
            "duration_s": round(time.monotonic() - t0, 1),
        }

    def _rpc_nodes_started(self) -> list:
        return [n for n in self._rpc_nodes() if n.proc is not None]

    def _wait_caught_up(self, node, timeout: float = 90.0) -> None:
        """Block until the (just-perturbed) node is back within live
        height_spread reach of the fleet head — the watch holds for
        the whole recovery, or a fast chain sprints away from a
        blocksync-ing victim and trips height_spread the moment
        evaluation resumes (seen live at ~3 blocks/s)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            others = [n for n in self._rpc_nodes_started() if n is not node]
            if not others or node.height() >= self._max_height(others) - 2:
                return
            time.sleep(0.3)
        raise TimeoutError(
            f"{node.m.name} never caught back up to the fleet head "
            f"(h={node.height()} vs {self._max_height(self._rpc_nodes_started())})"
        )

    def soak_report(self) -> dict:
        """Post-scenario facts the acceptance paths assert on, gathered
        while the fleet is still alive (before cleanup): who PRUNED
        (earliest served block above genesis on a non-statesync node),
        who RESTORED via statesync (chunks actually applied, from the
        node's own /metrics), bank supply conservation, and light-proxy
        verification progress."""
        import urllib.request

        out: dict = {"pruned": [], "statesync_restored": [], "bank": None, "light": [],
                     "state": {"nodes": [], "light_read": None}, "device": []}
        for node in self._rpc_nodes():
            try:
                st = node.client().call("status")["sync_info"]
            except Exception:
                continue
            earliest = int(st.get("earliest_block_height") or 0)
            latest = int(st.get("latest_block_height") or 0)
            if earliest > self.manifest.initial_height and not node.m.state_sync:
                out["pruned"].append(
                    {"node": node.m.name, "earliest": earliest, "latest": latest}
                )
            if node.m.state_sync and node.prom_port:
                try:
                    body = urllib.request.urlopen(
                        f"http://127.0.0.1:{node.prom_port}/metrics", timeout=5
                    ).read().decode()
                    chunks = 0.0
                    for line in body.splitlines():
                        if line.startswith("tendermint_statesync_chunks_applied"):
                            chunks = float(line.rsplit(" ", 1)[1])
                    if chunks > 0:
                        out["statesync_restored"].append(
                            {"node": node.m.name, "chunks_applied": int(chunks),
                             "earliest": earliest}
                        )
                except Exception:  # noqa: BLE001 - report is evidence, not a gate
                    pass
        if self.manifest.app == "bank":
            try:
                import base64

                client = self._rpc_nodes()[0].client()
                res = client.call("abci_query", path="/supply", data="")
                out["bank"] = json.loads(base64.b64decode(res["response"]["value"]))
                # the tx indexer must HOLD the committed transfers —
                # the ROADMAP-4 "indexer sees non-trivial state" claim,
                # probed through the events query language
                found = client.call(
                    "tx_search", query="transfer.sender EXISTS", per_page=1
                )
                out["bank"]["indexed_transfers"] = int(found["total_count"])
            except Exception as e:  # noqa: BLE001
                out["bank"] = {"error": f"{type(e).__name__}: {e}"}
        if self.manifest.app == "bank":
            # tmstate evidence (docs/state.md): every consensus node's
            # incremental state plane emitted nonzero tendermint_state_
            # series, and a light proxy served a VERIFIED state_batch
            # read against its own verified head
            for node in self._rpc_nodes():
                if not node.prom_port:
                    continue
                try:
                    body = urllib.request.urlopen(
                        f"http://127.0.0.1:{node.prom_port}/metrics", timeout=5
                    ).read().decode()
                except Exception:  # noqa: BLE001 - report is evidence, not a gate
                    continue
                series = 0
                for line in body.splitlines():
                    if line.startswith("tendermint_state_") and not line.startswith("#"):
                        try:
                            if float(line.rsplit(" ", 1)[1]) > 0:
                                series += 1
                        except ValueError:
                            pass
                out["state"]["nodes"].append({"node": node.m.name, "series": series})
            lights = [n for n in self.nodes if n.m.mode == "light" and n.proc is not None]
            if lights:
                try:
                    from ..abci.bank import treasury_priv
                    from ..crypto.ed25519 import address_hash

                    addr = address_hash(treasury_priv(self.manifest.chain_id).pub_key().bytes())
                    key = b"acct:" + addr.hex().encode()
                    h = int(self._rpc_nodes()[0].client().call(
                        "status")["sync_info"]["latest_block_height"])
                    res = lights[0].client().call(
                        "state_batch", height=str(h), keys=[key.hex()])
                    out["state"]["light_read"] = {
                        "node": lights[0].m.name, "height": h,
                        "keys": len(res.get("keys") or []),
                        "root": res.get("root", ""),
                    }
                except Exception as e:  # noqa: BLE001
                    out["state"]["light_read"] = {"error": f"{type(e).__name__}: {e}"}
        if os.environ.get("TM_TPU_DEVOBS", "").strip().lower() in (
            "1", "on", "true", "yes",
        ):
            # tmdev evidence (docs/observability.md#tmdev): every
            # consensus node's device observatory exposed nonzero
            # tendermint_device_* series (the verify engine compiled
            # and moved bytes), plus its compile count + transfer
            # bytes for the report
            for node in self._rpc_nodes():
                if not node.prom_port:
                    continue
                try:
                    body = urllib.request.urlopen(
                        f"http://127.0.0.1:{node.prom_port}/metrics", timeout=5
                    ).read().decode()
                except Exception:  # noqa: BLE001 - report is evidence, not a gate
                    continue
                series = 0
                compiles = 0.0
                xfer = 0.0
                for line in body.splitlines():
                    if not line.startswith("tendermint_device_") or line.startswith("#"):
                        continue
                    try:
                        v = float(line.rsplit(" ", 1)[1])
                    except ValueError:
                        continue
                    if v > 0:
                        series += 1
                    if line.startswith("tendermint_device_compiles_total"):
                        compiles += v
                    elif line.startswith("tendermint_device_transfer_bytes_total"):
                        xfer += v
                out["device"].append({
                    "node": node.m.name, "series": series,
                    "compiles": int(compiles), "transfer_bytes": int(xfer),
                })
        for node in self.nodes:
            if node.m.mode != "light":
                continue
            heads = 0
            try:
                with open(os.path.join(node.home, "light.log")) as f:
                    heads = sum(1 for line in f if line.startswith("verified head"))
            except OSError:
                pass
            row = {"node": node.m.name, "verified_heads": heads}
            # the cmd_light --report file: proxy divergences (refused
            # forged headers / substituted proofs) + update errors —
            # the byz acceptance surface for header_forge runs
            try:
                with open(os.path.join(node.home, "light_divergence.json")) as f:
                    rep = json.load(f)
                row["divergences"] = int(rep.get(
                    "divergences", rep.get("proxy", {}).get("divergences", 0)))
                row["update_errors"] = int(rep.get("update_errors", 0))
            except (OSError, ValueError):
                pass
            out["light"].append(row)
        return out

    # ------------------------------------------------------------------ wait

    def wait_for_height(self, height: int, nodes=None, timeout: float = 120.0) -> None:
        deadline = time.monotonic() + timeout
        nodes = self._rpc_nodes(nodes)
        while time.monotonic() < deadline:
            self.check_watch()
            if all(n.height() >= height for n in nodes):
                return
            time.sleep(0.2)
        raise TimeoutError(
            f"heights {[(n.m.name, n.height()) for n in nodes]} never reached {height}"
        )

    def wait_progress(self, node: E2ENode, timeout: float = 90.0) -> None:
        """Node is back up and advancing."""
        deadline = time.monotonic() + timeout
        h0 = -1
        while time.monotonic() < deadline:
            self.check_watch()
            if node.proc is not None and node.proc.poll() is not None:
                # The node DIED mid-scenario rather than stalling:
                # grab evidence from the survivors NOW (their state at
                # the moment of death, not after another 90s of
                # drift), then fail fast — a dead process will never
                # advance out this loop. The victim itself can't be
                # scraped anymore; its collector-cached last scrape is
                # the freshest telemetry it left (kills the runner
                # didn't initiate have no pre-kill snapshot).
                self._persist_last_watch(node)
                try:
                    self.collect_artifacts(suffix=".on-death")
                except Exception as e:  # noqa: BLE001 - evidence only
                    self.log(f"on-death artifact sweep failed: {e}")
                raise RuntimeError(
                    f"{node.m.name} exited (rc={node.proc.returncode}) during "
                    f"the scenario; survivor artifacts in *.on-death files"
                )
            h = node.height()
            if h0 < 0 and h >= 0:
                h0 = h
            elif h0 >= 0 and h > h0:
                return
            time.sleep(0.2)
        raise TimeoutError(f"{node.m.name} not advancing after perturbation (h={node.height()})")

    # ------------------------------------------------------------------ test

    def check_consistency(self) -> None:
        """All nodes agree on every committed block hash
        (ref: test/e2e/tests/block_test.go)."""
        heights = [n.height() for n in self._rpc_nodes() if n.height() >= 0]
        h = min(heights)
        assert h >= 1, f"no committed blocks: {heights}"
        for probe in range(max(1, h - 3), h + 1):
            hashes = set()
            for n in self.nodes:
                try:
                    res = n.client().call("block", height=str(probe))
                    hashes.add(res["block_id"]["hash"])
                except Exception:
                    continue
            assert len(hashes) == 1, f"divergent block {probe}: {hashes}"

    def benchmark(self, blocks: int = 10) -> dict:
        """Block cadence stats (ref: runner/benchmark.go:16-60)."""
        client = self._rpc_nodes()[0].client()
        status = client.call("status")
        to = int(status["sync_info"]["latest_block_height"])
        frm = max(self.manifest.initial_height, to - blocks)
        times = []
        for h in range(frm, to + 1):
            meta = client.call("block", height=str(h))
            times.append(Time.parse_rfc3339(meta["block"]["header"]["time"]).unix_ns())
        deltas = [(b - a) / 1e9 for a, b in zip(times, times[1:])]
        return {
            "blocks": len(deltas),
            "avg_interval_s": round(statistics.mean(deltas), 4) if deltas else None,
            "stddev_s": round(statistics.pstdev(deltas), 4) if len(deltas) > 1 else 0.0,
            "min_s": round(min(deltas), 4) if deltas else None,
            "max_s": round(max(deltas), 4) if deltas else None,
        }

    # ----------------------------------------------------------------- stop

    def collect_artifacts(self, nodes=None, suffix: str = "") -> None:
        """Persist each live node's observability state into its home
        dir: the /metrics exposition text (metrics{suffix}.txt) and,
        when span tracing is active in the nodes (TM_TPU_TRACE in the
        runner env propagates), the Chrome-trace snapshot from the
        dump_traces RPC (trace{suffix}.json). Best-effort — a node that
        is already dead cannot be scraped and simply contributes no
        artifact (its previous life may have left a .pre-* snapshot via
        perturb()). Callable mid-run: `nodes` restricts the sweep,
        `suffix` keeps a snapshot from being overwritten by the final
        collection."""
        import urllib.request

        for node in nodes if nodes is not None else self.nodes:
            if node.proc is None or node.proc.poll() is not None:
                self.log(f"{node.m.name}: dead ({'never started' if node.proc is None else 'exited'}); no artifacts to collect")
                continue
            if node.prom_port and node.m.mode not in ("seed", "light"):
                try:
                    body = urllib.request.urlopen(
                        f"http://127.0.0.1:{node.prom_port}/metrics", timeout=5
                    ).read()
                    with open(os.path.join(node.home, f"metrics{suffix}.txt"), "wb") as f:
                        f.write(body)
                except Exception as e:  # noqa: BLE001 - artifact only
                    self.log(f"metrics scrape failed for {node.m.name}: {e}")
            if node.m.mode not in ("seed", "light"):
                try:
                    res = node.client().call("dump_traces")
                    if res.get("events"):
                        with open(os.path.join(node.home, f"trace{suffix}.json"), "w") as f:
                            json.dump(res["trace"], f)
                except Exception as e:  # noqa: BLE001 - artifact only
                    self.log(f"trace dump failed for {node.m.name}: {e}")

    def analyze_artifacts(self, gates: dict | None = None):
        """Run tmlens over the collected run directory: write
        fleet_report.json (+ fleet_trace.json when any node left a
        trace), log the human summary, and return the report. This is
        the ROADMAP-4 gate: the slow e2e tests assert
        `runner.last_report["verdict"]`. A live-watch abort is folded
        in: the tripped gate's entry is forced to FAIL (the final
        scrapes may look healthy — they were taken seconds into the
        failure, before the post-mortem thresholds could accumulate)
        and the verdict names it. Never raises — a broken analyzer
        must not mask the run's own failure in a finally block."""
        try:
            from ..lens import REPORT_NAME, analyze_run, render_summary, write_merged_trace

            report = analyze_run(self.base_dir, gates=gates)
            if self.watch_tripped is not None:
                report["live_abort"] = {
                    k: v for k, v in self.watch_tripped.items() if k != "all"
                }
                live_by_name = {
                    g["name"]: g for g in self.watch_tripped.get("all", [])
                } or {self.watch_tripped["gate"]: self.watch_tripped}
                matched = set()
                for g in report["gates"]:
                    live = live_by_name.get(g["name"])
                    if live is not None:
                        g["ok"] = False
                        g["detail"] = f"live watch abort: {live['detail']}"
                        matched.add(g["name"])
                for name, live in live_by_name.items():
                    if name not in matched:  # live-only gate name
                        report["gates"].append({
                            "name": name, "ok": False,
                            "detail": f"live watch abort: {live['detail']}",
                        })
                report["verdict"] = "fail"
            with open(os.path.join(self.base_dir, REPORT_NAME), "w") as f:
                json.dump(report, f, indent=1)
            merged = write_merged_trace(self.base_dir)
            if merged:
                self.log(f"merged fleet trace: {merged}")
            self.log(render_summary(report))
            self.last_report = report
            return report
        except Exception as e:  # noqa: BLE001 - verdict is advisory here
            self.log(f"tmlens analysis failed: {type(e).__name__}: {e}")
            return None

    def cleanup(self) -> None:
        self.stop_watch()
        # nodes that are already dead can't serve the final scrape
        # below; their collector-cached last scrape is the fallback
        for node in self.nodes:
            if node.proc is not None and node.proc.poll() is not None:
                self._persist_last_watch(node)
        try:
            self.collect_artifacts()
        except Exception as e:  # noqa: BLE001 - teardown must proceed
            self.log(f"artifact collection failed: {e}")
        if self.faultnet is not None:
            self.faultnet.close()
        for node in self.nodes:
            for proc in (node.proc, node.app_proc):
                if proc is not None and proc.poll() is None:
                    proc.send_signal(signal.SIGCONT)  # in case it's paused
                    proc.terminate()
        deadline = time.monotonic() + 10
        for node in self.nodes:
            for proc in (node.proc, node.app_proc):
                if proc is None:
                    continue
                try:
                    proc.wait(timeout=max(0.1, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    proc.kill()
        # analysis runs AFTER the processes exit so profile.collapsed
        # files (TM_TPU_PROF=1 nodes write them on shutdown) are on disk
        if self.nodes and os.path.isdir(self.base_dir):
            self.analyze_artifacts()


def run_soak(manifest_path: str, base_dir: str, duration: float = 30.0,
             cores: int | None = None, gates: dict | None = None,
             logger=print) -> tuple["Runner", dict]:
    """One full soak cycle (ISSUE 14): parse → core-gate → setup →
    start (statesync_join nodes deferred to the timeline) → soak →
    soak_report → cleanup (tmlens verdict). Returns (runner, summary);
    runner.last_report carries the gated fleet verdict after cleanup.
    scripts/tmsoak.py and the slow soak test are thin wrappers."""
    from .scenario import FULL_MIX_CORES, gate_overrides_for, resolve_for_cores

    with open(manifest_path) as f:
        manifest = Manifest.parse(f.read())
    manifest, timeline, notes = resolve_for_cores(manifest, cores=cores)
    for note in notes:
        logger(f"core-gate: {note}")
    runner = Runner(manifest, base_dir, logger=logger)
    eff_cores = cores if cores is not None else (os.cpu_count() or 1)
    small_box = eff_cores < FULL_MIX_CORES
    if small_box:
        # the core gate's device-plane half: on a small box every node
        # runs the engine's host plane outright — the jax import
        # (~15s of CPU per process) and accelerator probes otherwise
        # steal exactly the core consensus needs, mid-run, every time a
        # node (re)starts or a late joiner boots (docs/e2e.md)
        for k, v in (("TM_TPU_CRYPTO", "off"), ("TM_TPU_AUTOTUNE", "off")):
            runner.extra_node_env.setdefault(k, os.environ.get(k, v))
        logger(f"core-gate: {eff_cores} core(s) < {FULL_MIX_CORES}: nodes "
               "pinned to the host crypto plane (no jax import)")
    # budget half of core-aware resolution: stall/head-age budgets
    # scaled to the box (docs/e2e.md#core-gating); explicit caller
    # gates still win. Caller keys the rolling watch recognizes
    # (WATCH_DEFAULTS) override the LIVE budgets too — a run that
    # legitimately pauses longer than the scaled stall window (first
    # XLA compile with the device crypto plane forced on a small box)
    # needs the live gate widened, not just the post-mortem one.
    # Watch-only keys never reach gates.evaluate, which refuses
    # unknown keys loudly.
    from ..lens.gates import DEFAULT_GATES
    from ..lens.series import WATCH_DEFAULTS

    post_gates, watch_gates = gate_overrides_for(eff_cores)
    for k, v in (gates or {}).items():
        if k in WATCH_DEFAULTS:
            watch_gates[k] = v
        if k in DEFAULT_GATES or k not in WATCH_DEFAULTS:
            post_gates[k] = v
    if watch_gates:
        logger(f"core-gate: budgets scaled for {eff_cores} core(s): "
               f"post-mortem {post_gates}, live {watch_gates}")
    runner.setup()
    summary: dict = {}
    try:
        defer = {
            name
            for act in timeline.resolve(manifest)
            if act["kind"] == "statesync_join"
            for name in act["nodes"]
        }
        runner.start(defer=defer)
        summary = runner.soak(
            duration, timeline,
            perturb_timeout=180.0 if small_box else 90.0,
            watch_gates=watch_gates or None,
        )
        summary["core_gate_notes"] = notes
        summary["soak_report"] = runner.soak_report()
        logger(f"soak summary: {json.dumps(summary['soak_report'])}")
    finally:
        runner.cleanup()
        if post_gates and runner.nodes and os.path.isdir(runner.base_dir):
            # cleanup analyzed with the defaults; re-run the verdict
            # plane with the box-scaled (+ caller) thresholds
            runner.analyze_artifacts(gates=post_gates)
    return runner, summary


def run_manifest(manifest_path: str, base_dir: str, duration: float = 10.0) -> dict:
    """One full e2e cycle: setup → start → load+perturb → test →
    benchmark → cleanup (ref: runner/main.go)."""
    with open(manifest_path) as f:
        manifest = Manifest.parse(f.read())
    runner = Runner(manifest, base_dir)
    runner.setup()
    try:
        runner.start()
        # live rolling gates for the rest of the run: a stall/storm
        # aborts here (WatchTripped) instead of timing out downstream
        runner.start_watch()
        runner.wait_for_height(2)
        import threading

        load_thread = threading.Thread(target=runner.inject_load, args=(duration,), daemon=True)
        load_thread.start()
        if manifest.flood_txs:
            runner.inject_flood()
        runner.apply_validator_updates()
        runner.run_perturbations()
        load_thread.join(timeout=duration + 10)
        h = max(n.height() for n in runner.nodes)
        runner.wait_for_height(h + 2)
        runner.check_consistency()
        bench = runner.benchmark()
        print(json.dumps(bench))
        return bench
    finally:
        runner.cleanup()
