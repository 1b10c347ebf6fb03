"""Closed loop, one joiner, one serving peer, in one process: a node
with empty stores block-syncs the seeded chain from a peer over
`p2p.MemoryNetwork`, through the real pool, `BlockSyncReactor`
(verify-ahead included), `types/validation`, `ops/engine`,
`apply_block` and the stores. The shape of `blocksync/fixture.py`
`sync`, driven for a fixed time instead of to the end.

Parameters (`benchmark/traffic/<mix>.json`):
    warm_up_blocks     blocks the warm-up joiner applies before it is stopped
    check_sample       applied heights of each pass the reference follows
    refusal_heights    [lo, hi]: the corrupted commit's height is drawn from it
"""

from __future__ import annotations

import hashlib
import random
import threading
import time

from benchmark import chain as chainlib
from benchmark import reference as ref
from benchmark.drivers import Check


class Pass:
    """One joiner from empty stores against one serving peer."""

    def __init__(self, chain, serve_from=None, stop_on_peer_error=False):
        from tendermint_tpu.abci import LocalClient
        from tendermint_tpu.abci.kvstore import KVStoreApplication
        from tendermint_tpu.p2p import MemoryNetwork
        from tendermint_tpu.state import BlockExecutor, StateStore, make_genesis_state
        from tendermint_tpu.store.blockstore import BlockStore
        from tendermint_tpu.store.kv import MemDB

        self.chain = chain
        self.done = threading.Event()
        self.caught_up = False
        self.fatal = None
        self.peer_errors: list = []
        self.counted = None  # blocks applied when the pass was counted
        state = make_genesis_state(chain.gen_doc)
        state_store, self.block_store = StateStore(MemDB()), BlockStore(MemDB())
        state_store.save(state)
        executor = BlockExecutor(state_store, LocalClient(KVStoreApplication()),
                                 block_store=self.block_store)
        net = MemoryNetwork()
        source_exec = BlockExecutor(chain.state_store, LocalClient(KVStoreApplication()))
        self.server = _Peer(net, b"bench-server", chain.chain_id, chain.state, source_exec,
                            chain.block_store if serve_from is None else serve_from,
                            block_sync=False)
        self.joiner = _Peer(net, b"bench-joiner", chain.chain_id, state, executor,
                            self.block_store, on_caught_up=self._on_caught_up,
                            on_fatal=self._on_fatal)
        send_error = self.joiner.channel.send_error

        def record_error(peer_error):
            self.peer_errors.append(peer_error)
            send_error(peer_error)
            if stop_on_peer_error:
                self.done.set()

        self.joiner.channel.send_error = record_error

    def _on_caught_up(self, _state, _n):
        self.caught_up = True
        self.done.set()

    def _on_fatal(self, exc):
        self.fatal = exc
        self.done.set()

    @property
    def reactor(self):
        return self.joiner.reactor

    def start(self) -> None:
        from tendermint_tpu.p2p.transport import Endpoint

        self.server.start()
        self.joiner.start()
        self.joiner.pm.add(Endpoint(protocol="memory", host=self.server.node_id,
                                    node_id=self.server.node_id))

    def stop(self) -> int:
        """Count the blocks applied so far, unless the window already
        has, then stop both nodes and wait for the joiner's threads, so
        that its stores are still."""
        if self.counted is None:
            self.counted = self.reactor.blocks_synced
        self.joiner.stop()
        self.server.stop()
        for t in self.reactor._threads:
            t.join(timeout=30.0)
        return self.counted


class _Peer:
    """One end of the in-process network, carrying only the blocksync
    reactor."""

    def __init__(self, network, key_seed, chain_id, state, block_exec, block_store, **reactor_kw):
        from tendermint_tpu.blocksync.reactor import (
            BlockSyncReactor,
            blocksync_channel_descriptor,
        )
        from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
        from tendermint_tpu.p2p import (
            NodeInfo,
            PeerManager,
            PeerManagerOptions,
            Router,
            node_id_from_pubkey,
        )

        key = Ed25519PrivKey.generate(hashlib.sha256(key_seed).digest())
        self.node_id = node_id_from_pubkey(key.pub_key())
        self.pm = PeerManager(self.node_id, PeerManagerOptions(max_connected=8))
        self.router = Router(NodeInfo(node_id=self.node_id, network=chain_id), key, self.pm,
                             [network.create_transport(self.node_id)])
        self.channel = self.router.open_channel(blocksync_channel_descriptor())
        self.reactor = BlockSyncReactor(state, block_exec, block_store, self.channel, self.pm,
                                        **reactor_kw)

    def start(self) -> None:
        self.router.start()
        self.reactor.start()

    def stop(self) -> None:
        self.reactor.stop()
        self.router.stop()


class Traffic:
    def __init__(self, config: dict, params: dict, seed: int):
        self.config, self.params, self.seed = config, params, seed
        self.chain = None
        self.passes: list[Pass] = []

    def build(self) -> None:
        self.chain = chainlib.build(self.config, self.seed)

    def warm_up(self) -> None:
        """A short sync: the joiner's first blocks run every program and
        every thread the window uses."""
        want = min(self.params["warm_up_blocks"], self.chain.height - 1)
        p = Pass(self.chain)
        p.start()
        deadline = time.monotonic() + 600.0
        while p.reactor.blocks_synced < want and not p.done.is_set():
            if time.monotonic() > deadline:
                break
            time.sleep(0.01)
        got = p.stop()
        if p.fatal is not None or p.peer_errors or got < want:
            raise RuntimeError(f"warm-up sync applied {got} of {want} blocks: "
                               f"fatal={p.fatal!r} peer_errors={p.peer_errors}")

    def window(self, seconds: float) -> dict:
        """Passes back to back until the deadline; the running pass is
        stopped there and the blocks it has applied are counted."""
        t0 = time.perf_counter()
        deadline = t0 + seconds
        progress = []  # blocks applied so far, every half second: a stall shows
        while True:
            p = Pass(self.chain)
            self.passes.append(p)
            p.start()
            while not p.done.wait(min(0.5, max(0.0, deadline - time.perf_counter()))):
                if time.perf_counter() >= deadline:
                    break
                progress.append(p.reactor.blocks_synced)
            p.counted = p.reactor.blocks_synced
            t1 = time.perf_counter()
            p.stop()
            if t1 >= deadline or p.fatal is not None:
                break
        return {"ops": sum(p.counted for p in self.passes), "window_s": t1 - t0,
                "passes": len(self.passes),
                "passes_to_the_end": sum(p.caught_up for p in self.passes),
                "progress": " ".join(map(str, progress))}

    # ------------------------------------------------------------- correct

    def check(self) -> tuple[list[Check], int, int]:
        """(checks, attempted, failed): every block the window's joiners
        applied against the source and the reference, then one commit
        that must be refused."""
        chain, rng = self.chain, random.Random(self.seed)
        wrong_hash = wrong_header = wrong_commit = wrong_app = halted = 0
        applied = 0
        for p in self.passes:
            height = p.block_store.height()
            applied += height
            if p.fatal is not None or p.peer_errors:
                halted += 1
            for h in range(1, height + 1):
                meta = p.block_store.load_block_meta(h)
                wrong_hash += meta is None or meta.block_id.hash != chain.block_hashes[h - 1]
            if height == 0:
                continue
            state = p.reactor.state
            wrong_app += (state.last_block_height != height
                          or state.app_hash != ref.kvstore_app_hash(chain.txs_per_block * height))
            sample = set(rng.sample(range(1, height + 1),
                                    min(self.params["check_sample"], height)))
            sample.add(height)
            for h in sorted(sample):
                block = p.block_store.load_block(h)
                wrong_header += (ref.header_hash(chainlib.header_values(block.header))
                                 != chain.block_hashes[h - 1])
                # the commit blocksync proved h with (the light rule) ...
                sigs, msgs = chainlib.commit_values(chain, p.block_store.load_seen_commit(h))
                ok, _ = ref.commit_verdict(chain.pubkeys, chain.powers, sigs, msgs, 2, 3, True)
                wrong_commit += not ok
                # ... and the one apply_block validated in full
                if h > 1:
                    sigs, msgs = chainlib.commit_values(chain, block.last_commit)
                    ok, _ = ref.commit_verdict(chain.pubkeys, chain.powers, sigs, msgs, 2, 3,
                                               False)
                    wrong_commit += not ok
        refusal = self._refusal(rng)
        checks = [
            Check("blocks_differing_from_source", wrong_hash, 0),
            Check("headers_differing_from_reference_hash", wrong_header, 0),
            Check("applied_commits_the_reference_refuses", wrong_commit, 0),
            Check("app_hash_or_height_wrong", wrong_app, 0),
            Check("passes_halted_or_blaming_an_honest_peer", halted, 0),
            Check("refusal_faults", refusal, 0),
        ]
        failed = wrong_hash + wrong_header + wrong_commit + wrong_app + halted + refusal
        return checks, applied + 2, failed

    def _refusal(self, rng) -> int:
        """Twice, a peer serves a commit with one signature the curve
        equation refuses: once in the first half of what
        VerifyCommitLight checks, once in the second, so that a
        verification that leaves either half out is seen. Each time the
        joiner must stop below that height, blame the peer with a
        verdict and not halt, and the reference must refuse the same
        commit. Returns the number of those that went wrong."""
        chain = self.chain
        lo, hi = self.params["refusal_heights"]
        hi = min(hi, chain.height - 2)
        prefix = chainlib.signing_prefix(chain, 2, 3)
        faults, self.refusal = 0, []
        for half in (range(prefix // 2), range(prefix // 2, prefix)):
            commit_height = rng.randint(min(lo, hi), hi)
            bad_index = rng.choice(half)
            served = chainlib.corrupted_store(chain, commit_height, bad_index)
            sigs, msgs = chainlib.commit_values(
                chain, served.load_block(commit_height + 1).last_commit)
            accepted, _ = ref.commit_verdict(chain.pubkeys, chain.powers, sigs, msgs, 2, 3, True)
            p = Pass(chain, serve_from=served, stop_on_peer_error=True)
            p.start()
            p.done.wait(600.0)
            p.stop()
            self.refusal.append({
                "commit_height": commit_height, "bad_index": bad_index,
                "reference_accepts": accepted, "joiner_height": p.block_store.height(),
                "fatal": repr(p.fatal) if p.fatal is not None else None,
                "peer_errors": [f"{type(e.err).__name__}: {str(e.err)[:40]}"
                                for e in p.peer_errors],
            })
            faults += int(accepted)
            faults += p.fatal is not None
            faults += p.block_store.height() != commit_height - 1
            faults += not (p.peer_errors and isinstance(p.peer_errors[0].err, ValueError)
                           and f"wrong signature (#{bad_index})" in str(p.peer_errors[0].err))
        return faults
