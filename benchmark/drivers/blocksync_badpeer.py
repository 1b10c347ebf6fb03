"""Closed loop, one joiner, several serving peers of which some lie, in
one process: `benchmark/drivers/blocksync.py`'s pass with the
configuration's `peers` (`serving` nodes, `lying` of them) in place of
its one honest peer. The joiner dials them all over one
`p2p.MemoryNetwork`; the honest ones answer from the chain's store, a
liar from `benchmark/chain_badpeer.py`'s. What the joiner refuses, whom
it blames, what its pool throws away and how long a blamed peer stays
out are read from the reactor's own `BlockSyncMetrics` over the whole
window (a 1.25 s slice holds one refusal or none) and handed to the
readers in `window`.

Parameters (`benchmark/traffic/<mix>.json`): `blocksync`'s, with
    warm_up_blocks     blocks the warm-up joiner applies before it is stopped; a refusal
                       of each kind of lie the schedule holds is warmed up after it
"""

from __future__ import annotations

import random
import sys
import threading
import time
from dataclasses import dataclass, field

from benchmark import chain as chainlib
from benchmark import chain_badpeer
from benchmark import reference as ref
from benchmark import reference_badpeer as refbad
from benchmark.drivers import Check
from benchmark.drivers import blocksync as base

COUNTERS = {  # BlockSyncMetrics attribute -> the window's key, one a label where it has any
    "refusals": "refusals_{}", "refusal_seconds": "refusal_s",
    "blocks_dropped": "blocks_dropped", "blocks_received": "blocks_received",
    "peer_returns": "peer_returns", "peer_out_seconds": "peer_out_s",
    "verify_ahead": "verify_ahead_{}",
}
ZEROS = ("refusals_commit", "refusals_block", "refusal_s", "blocks_dropped", "blocks_received",
         "peer_returns", "peer_out_s", "verify_ahead_used", "verify_ahead_stale")


@dataclass
class Refusal:
    """One pair the joiner refused, as the driver saw it happen."""

    pair: int  # the pool's height when the blame was sent: the pair's lower height
    stage: str  # which of the program's refusal counters had moved
    err: Exception
    peers: list[str] = field(default_factory=list)


class Pass:
    """One joiner from empty stores against `stores`, one serving peer
    each; the peers that serve `liar_store` are the liars."""

    def __init__(self, chain, stores: list, liar_store=None):
        from tendermint_tpu.abci import LocalClient
        from tendermint_tpu.abci.kvstore import KVStoreApplication
        from tendermint_tpu.metrics import BlockSyncMetrics, Registry
        from tendermint_tpu.p2p import MemoryNetwork
        from tendermint_tpu.state import BlockExecutor, StateStore, make_genesis_state
        from tendermint_tpu.store.blockstore import BlockStore
        from tendermint_tpu.store.kv import MemDB

        self.chain = chain
        self.done = threading.Event()
        self.caught_up = False
        self.fatal = None
        self.counted = None  # blocks applied when the pass was counted
        self.refusals: list[Refusal] = []
        self.other_blames: list[tuple[str, Exception]] = []  # not the verify loop's
        self.metrics = BlockSyncMetrics(Registry())
        state = make_genesis_state(chain.gen_doc)
        state_store, self.block_store = StateStore(MemDB()), BlockStore(MemDB())
        state_store.save(state)
        executor = BlockExecutor(state_store, LocalClient(KVStoreApplication()),
                                 block_store=self.block_store)
        net = MemoryNetwork()
        source_exec = BlockExecutor(chain.state_store, LocalClient(KVStoreApplication()))
        self.servers = [
            base._Peer(net, b"bench-server-%d" % i, chain.chain_id, chain.state, source_exec,
                       store, block_sync=False)
            for i, store in enumerate(stores)]
        self.liars = {server.node_id for server, store in zip(self.servers, stores)
                      if store is liar_store}
        self.joiner = base._Peer(net, b"bench-joiner", chain.chain_id, state, executor,
                                 self.block_store, on_caught_up=self._on_caught_up,
                                 on_fatal=self._on_fatal, metrics=self.metrics)
        send_error = self.joiner.channel.send_error
        seen = {"commit": 0.0, "block": 0.0}

        def record_error(peer_error):
            """The verify loop blames from its own thread (`bs-pool`,
            as `BlockSyncReactor.start` names it), the pool's height
            still at the pair's lower block, its refusal counter already
            moved; both blames of a pair carry one error."""
            if threading.current_thread().name != "bs-pool":
                self.other_blames.append((peer_error.node_id, peer_error.err))
            elif self.refusals and self.refusals[-1].err is peer_error.err:
                self.refusals[-1].peers.append(peer_error.node_id)
            else:
                now = {stage: self.counters().get("refusals_" + stage, 0.0) for stage in seen}
                moved = [stage for stage in seen if now[stage] != seen[stage]]
                seen.update(now)
                self.refusals.append(Refusal(self.reactor.pool.height, "+".join(moved) or "none",
                                             peer_error.err, [peer_error.node_id]))
            send_error(peer_error)

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

    def counters(self) -> dict:
        """The joiner's blocksync counters under the window's keys."""
        out = {}
        for attr, key in COUNTERS.items():
            for _, labels, value in getattr(self.metrics, attr).samples():
                out[key.format(*labels.values())] = value
        return out

    def start(self) -> None:
        from tendermint_tpu.p2p.transport import Endpoint

        for server in self.servers:
            server.start()
        self.joiner.start()
        for server in self.servers:
            self.joiner.pm.add(Endpoint(protocol="memory", host=server.node_id,
                                        node_id=server.node_id))

    def stop(self) -> int:
        """Count the blocks applied so far, unless the window already
        has, then stop every node and wait for the joiner's threads, so
        that its stores are still."""
        if self.counted is None:
            self.counted = self.reactor.blocks_synced
        # all at once: a router takes a few tenths of a second to stop, and the
        # window's next pass waits for this one's nodes
        stoppers = [threading.Thread(target=node.stop, name="bench-stop")
                    for node in [self.joiner, *self.servers]]
        for t in stoppers:
            t.start()
        for t in stoppers + self.reactor._threads:
            t.join(timeout=30.0)
        return self.counted


class Traffic:
    def __init__(self, config: dict, params: dict, seed: int):
        from tendermint_tpu.metrics import BlockSyncMetrics, Registry

        if not hasattr(BlockSyncMetrics(Registry()), "refusals"):
            # an exit and no result: this program cannot be held to the cell's guarantees
            print("benchmark: this program's BlockSyncMetrics counts no refusals: its joiner "
                  "cannot be judged against a lying peer", file=sys.stderr)
            sys.exit(2)
        self.config, self.params, self.seed = config, params, seed
        self.chain = self.stores = None
        self.lies = refbad.schedule(config, seed) if config["peers"]["lying"] else []
        self.refusable = refbad.refusable(self.lies)
        self.rows = {lie.height: lie.row for lie in self.lies}
        self.passes: list[Pass] = []
        self._verdicts: dict[tuple, bool] = {}  # the reference's, by the commit's own bytes

    def build(self) -> None:
        self.chain = chainlib.build(self.config, self.seed)
        peers = self.config["peers"]
        self.liar_store = chain_badpeer.liar_store(self.chain, self.lies)
        self.stores = ([self.chain.block_store] * (peers["serving"] - peers["lying"])
                       + [self.liar_store] * peers["lying"])

    def new_pass(self) -> Pass:
        return Pass(self.chain, self.stores, self.liar_store)

    # ----------------------------------------------------------- the rule

    def kind(self, refusal: Refusal) -> str | None:
        """Which of the schedule's lies this refusal is the reference's
        answer to: the pair holds one, the program refused it where the
        commit is checked, and its verdict names what the lie breaks.
        None: refused at the wrong stage, or for nothing the schedule
        holds."""
        allowed = self.refusable.get(refusal.pair, ())
        if refusal.stage != "commit":
            return None
        text = str(refusal.err)
        if (refbad.SIGNATURE in allowed
                and f"wrong signature (#{self.rows[refusal.pair + 1]})" in text):
            return refbad.SIGNATURE
        if refbad.BLOCK_ID in allowed and "wrong block ID" in text:
            return refbad.BLOCK_ID
        return None

    def accepted(self, commit, light: bool) -> bool:
        """`reference.commit_verdict` on a program Commit, by the light
        rule or in full; the passes of a window store the same commits,
        so a verdict is computed once for the bytes it is about."""
        sigs, msgs = chainlib.commit_values(self.chain, commit)
        key = (light, tuple(sigs), tuple(msgs))
        if key not in self._verdicts:
            chain = self.chain
            self._verdicts[key], _ = ref.commit_verdict(chain.pubkeys, chain.powers, sigs, msgs,
                                                        2, 3, light)
        return self._verdicts[key]

    def reference_refuses(self, pair: int, kind: str) -> bool:
        """The reference's own verdict on the commit the liar serves
        for this pair: by the light rule on block pair + 1's LastCommit,
        or in full on block pair's, whose bytes the block ID covers."""
        light = kind == refbad.SIGNATURE
        commit = self.liar_store.load_block(pair + 1 if light else pair).last_commit
        return not self.accepted(commit, light)

    def judge(self, p: Pass) -> dict:
        """A pass's blames against the rule: (kinds of lie refused,
        refusals at the wrong stage, blames outside the rule)."""
        kinds = {refbad.SIGNATURE: 0, refbad.BLOCK_ID: 0}
        wrong_stage = outside = 0
        blamed: set[str] = set()
        for r in p.refusals:
            kind = self.kind(r)
            blamed.update(r.peers)
            if r.pair not in self.refusable:
                outside += len(r.peers)
                continue
            if kind is None:
                wrong_stage += 1
                continue
            kinds[kind] += 1
            if (len(r.peers) > 2 or not p.liars & set(r.peers)
                    or not self.reference_refuses(r.pair, kind)):
                outside += len(r.peers)
        # the pool's own blames (a block it no longer waits for, a peer gone silent)
        # may name only a peer a refusal has named
        outside += sum(node_id not in blamed for node_id, _ in p.other_blames)
        return {"kinds": kinds, "wrong_stage": wrong_stage, "outside": outside}

    # ---------------------------------------------------------- the passes

    def warm_up(self) -> None:
        """A short sync from all the peers, which runs every program and
        thread the window uses, then every path of a refusal, each for a
        time that does not hang on what the peers happen to serve: for
        each kind of lie the schedule holds, a joiner whose only peer
        serves one such lie, until it has refused it and seen the peer
        it evicted come back."""
        want = min(self.params["warm_up_blocks"], self.chain.height - 1)
        p = self.new_pass()
        self._run_until(p, lambda: p.reactor.blocks_synced >= want)
        verdict = self.judge(p)
        if (p.fatal is not None or p.counted < want or verdict["wrong_stage"]
                or verdict["outside"]):
            raise RuntimeError(f"warm-up sync applied {p.counted} of {want} blocks: "
                               f"fatal={p.fatal!r} judged {verdict} refusals={p.refusals} "
                               f"others={p.other_blames}")
        prefix = refbad.light_prefix(self.chain.powers)
        rows = {refbad.SIGNATURE: prefix - 1, refbad.BLOCK_ID: prefix}
        for kind in sorted({lie.kind for lie in self.lies}):
            p = Pass(self.chain, [chainlib.corrupted_store(self.chain, 2, rows[kind])])
            self._run_until(p, lambda: p.refusals and p.counters().get("peer_returns", 0) > 0)
            if (p.fatal is not None or not p.refusals or not p.counters().get("peer_returns")
                    or {r.stage for r in p.refusals} != {"commit"}):
                raise RuntimeError(f"warm-up refusal ({kind}): fatal={p.fatal!r} "
                                   f"refusals={p.refusals} counters={p.counters()}")

    @staticmethod
    def _run_until(p: Pass, reached, seconds: float = 300.0) -> None:
        p.start()
        deadline = time.monotonic() + seconds
        while not reached() and not p.done.is_set() and time.monotonic() < deadline:
            time.sleep(0.01)
        p.stop()

    def window(self, seconds: float) -> dict:
        """Passes back to back until the deadline; the running pass is
        stopped there and the blocks it has applied are counted."""
        t0 = time.perf_counter()
        deadline = t0 + seconds
        progress = []  # blocks applied so far, every half second: a stall shows
        while True:
            p = self.new_pass()
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
        out = {"ops": sum(p.counted for p in self.passes), "window_s": t1 - t0,
               "passes": len(self.passes),
               "passes_to_the_end": sum(p.caught_up for p in self.passes)}
        out.update(dict.fromkeys(ZEROS, 0.0))
        for p in self.passes:
            for key, value in p.counters().items():
                out[key] = out.get(key, 0.0) + value
            for kind, n in self.judge(p)["kinds"].items():
                out["refused_for_" + kind] = out.get("refused_for_" + kind, 0) + n
            out["blames_by_the_pool"] = out.get("blames_by_the_pool", 0) + len(p.other_blames)
        out["progress"] = " ".join(map(str, progress))
        return out

    # ------------------------------------------------------------- correct

    def check(self) -> tuple[list[Check], int, int]:
        """(checks, attempted, failed): every block the window's joiners
        stored against the source and the reference, every blame against
        the rule, then three commits that must be refused."""
        chain, rng = self.chain, random.Random(self.seed)
        wrong_hash = wrong_header = wrong_commit = wrong_app = halted = 0
        wrong_stage = outside = applied = refused = 0
        for p in self.passes:
            height = p.block_store.height()
            applied += height
            halted += p.fatal is not None
            verdict = self.judge(p)
            wrong_stage += verdict["wrong_stage"]
            outside += verdict["outside"]
            refused += sum(verdict["kinds"].values())
            for h in range(1, height + 1):
                meta = p.block_store.load_block_meta(h)
                wrong_hash += meta is None or meta.block_id.hash != chain.block_hashes[h - 1]
            state = p.reactor.state
            # nothing persisted above the state, and the state where the reference has it
            wrong_app += state.last_block_height != height or (height > 0 and (
                state.app_hash != ref.kvstore_app_hash(chain.txs_per_block * height)))
            if height == 0:
                continue
            sample = set(rng.sample(range(1, height + 1),
                                    min(self.params["check_sample"], height)))
            sample.add(height)
            # ... and every height at which the liar's copy differs from the source's
            for h in sorted(sample | {h for h in self.rows if h <= height}):
                block = p.block_store.load_block(h)
                if h in sample:
                    wrong_header += (ref.header_hash(chainlib.header_values(block.header))
                                     != chain.block_hashes[h - 1])
                # the commit blocksync proved h with (the light rule) ...
                wrong_commit += not self.accepted(p.block_store.load_seen_commit(h), True)
                # ... and the one the block carries, validated in full before it was stored
                if h > 1:
                    wrong_commit += not self.accepted(block.last_commit, False)
        refusal = self._refusal(rng)
        checks = [
            Check("blocks_differing_from_source", wrong_hash, 0),
            Check("headers_differing_from_reference_hash", wrong_header, 0),
            Check("applied_commits_the_reference_refuses", wrong_commit, 0),
            Check("app_hash_or_height_wrong", wrong_app, 0),
            Check("passes_halted", halted, 0),
            Check("peers_blamed_outside_the_rule", outside, 0),
            Check("lies_caught_at_the_wrong_stage", wrong_stage, 0),
            Check("windows_without_a_refusal", int(bool(self.lies) and not refused), 0),
            Check("refusal_faults", refusal, 0),
        ]
        failed = sum(c.value for c in checks)
        return checks, applied + 3, failed

    def _refusal(self, rng) -> int:
        """Three times, a joiner's only peer serves a commit with one
        signature the curve equation refuses: in the first half of what
        VerifyCommitLight reads, in the second, and in the rows beyond
        it. The first two stop the joiner below the commit's height with
        `wrong signature (#i)`. The third passes the light rule, so the
        joiner applies that height and must refuse the next pair, whose
        lower block carries the commit: its bytes are not the ones that
        were signed. Each time the joiner blames the peer with a verdict
        and does not halt, its block store holds nothing above its
        state, and the reference refuses the same commit (by the light
        rule; for the third in full, the light rule accepting it).
        Returns the number of those that went wrong."""
        chain = self.chain
        lo, hi = self.params["refusal_heights"]
        hi = min(hi, chain.height - 3)
        prefix = refbad.light_prefix(chain.powers)
        faults, self.refusal = 0, []
        for rows in (range(prefix // 2), range(prefix // 2, prefix),
                     range(prefix, len(chain.powers))):
            beyond = rows.start >= prefix
            commit_height = rng.randint(min(lo, hi), hi)
            bad_index = rng.choice(rows)
            served = chainlib.corrupted_store(chain, commit_height, bad_index)
            sigs, msgs = chainlib.commit_values(
                chain, served.load_block(commit_height + 1).last_commit)
            light, _ = ref.commit_verdict(chain.pubkeys, chain.powers, sigs, msgs, 2, 3, True)
            full, _ = ref.commit_verdict(chain.pubkeys, chain.powers, sigs, msgs, 2, 3, False)
            p = base.Pass(chain, serve_from=served, stop_on_peer_error=True)
            p.start()
            p.done.wait(600.0)
            p.stop()
            stops_at = commit_height if beyond else commit_height - 1
            verdict = "wrong block ID" if beyond else f"wrong signature (#{bad_index})"
            self.refusal.append({
                "commit_height": commit_height, "bad_index": bad_index,
                "reference_accepts": [light, full], "joiner_height": p.block_store.height(),
                "state_height": p.reactor.state.last_block_height,
                "fatal": repr(p.fatal) if p.fatal is not None else None,
                "peer_errors": [f"{type(e.err).__name__}: {str(e.err)[:40]}"
                                for e in p.peer_errors],
            })
            faults += light != beyond
            faults += int(full)
            faults += p.fatal is not None
            faults += p.block_store.height() != stops_at
            faults += p.reactor.state.last_block_height != p.block_store.height()
            faults += not (p.peer_errors and isinstance(p.peer_errors[0].err, ValueError)
                           and verdict in str(p.peer_errors[0].err))
        return faults
