"""Closed loop, one light client: `light.client.LightClient` in
skipping mode verifies one new header per update against a primary and
witnesses serving the seeded chain, no validator-set churn.

The providers are the benchmark's own, in the shape of the mock
provider the reference's `light/client_benchmark_test.go` serves from:
each holds every light block as its wire encoding, made in set-up, and
decodes it on every fetch, as a client over RPC does. So every update
sees fresh objects with nothing memoised, the full node's store is not
in the measurement, and a walk of the chain costs the same the fifth
time as the first.

Parameters (`benchmark/traffic/<mix>.json`):
    skips              heights between one target and the next; the schedule is this
                       list repeated over the chain, shuffled by the seed, so every
                       seed walks the same steps in another order
    witnesses          providers cross-checked on every update
    trusting_period_s  the client's trusting period
    check_sample       updates the reference follows
"""

from __future__ import annotations

import gc
import random
import time

from benchmark import chain as chainlib
from benchmark import reference as ref
from benchmark.drivers import Check


def make_provider_class():
    from tendermint_tpu.light.provider import ErrLightBlockNotFound, Provider
    from tendermint_tpu.proto import messages as pb
    from tendermint_tpu.types.light_block import LightBlock

    class EncodedProvider(Provider):
        def __init__(self, chain_id: str, blocks: dict[int, bytes], name: str):
            self._chain_id, self.blocks, self.name = chain_id, blocks, name
            self.latest = max(blocks)

        def chain_id(self) -> str:
            return self._chain_id

        def id(self) -> str:
            return self.name

        def light_block(self, height: int):
            raw = self.blocks.get(height or self.latest)
            if raw is None:
                raise ErrLightBlockNotFound(f"no light block at height {height}")
            return LightBlock.from_proto(pb.LightBlock.decode(raw))

        def report_evidence(self, ev) -> None:
            pass

    return EncodedProvider


class Traffic:
    def __init__(self, config: dict, params: dict, seed: int):
        self.config, self.params, self.seed = config, params, seed
        self.chain = None
        self.encoded: dict[int, bytes] = {}
        self.schedule: list[int] = []
        self.clients: list = []  # every client of the window, with what it returned
        self.errors: list[str] = []

    def build(self) -> None:
        from tendermint_tpu.types.light_block import LightBlock, SignedHeader

        self.chain = chain = chainlib.build(self.config, self.seed)
        for h in range(1, chain.height + 1):
            lb = LightBlock(SignedHeader(chain.block_store.load_block_meta(h).header,
                                         chain.block_store.load_seen_commit(h)),
                            chain.validators)
            self.encoded[h] = lb.to_proto().encode()
        skips, steps, at = self.params["skips"], [], 1
        while at + skips[len(steps) % len(skips)] <= chain.height:
            steps.append(skips[len(steps) % len(skips)])
            at += steps[-1]
        random.Random(self.seed).shuffle(steps)
        at = 1
        for s in steps:
            at += s
            self.schedule.append(at)
        self.provider_class = make_provider_class()
        self.now_ns = chain.times_ns[-1] + 10**9

    def new_client(self, blocks=None):
        from tendermint_tpu.light.client import SKIPPING, LightClient, TrustOptions
        from tendermint_tpu.utils.tmtime import Time

        cls, chain = self.provider_class, self.chain
        primary = cls(chain.chain_id, blocks or self.encoded, "primary")
        witnesses = [cls(chain.chain_id, self.encoded, f"witness{i}")
                     for i in range(self.params["witnesses"])]
        now = Time.from_unix_ns(self.now_ns)
        return LightClient(
            chain.chain_id,
            TrustOptions(period_ns=self.params["trusting_period_s"] * 10**9, height=1,
                         hash=chain.block_hashes[0]),
            primary, witnesses, verification_mode=SKIPPING, clock=lambda: now,
        )

    def warm_up(self) -> None:
        """One walk of a client: every batch size, every program."""
        client = self.new_client()
        for height in self.schedule:
            client.verify_light_block_at_height(height)

    def window(self, seconds: float) -> dict:
        """Walk after walk until the deadline. A client's start (its
        trust root, one commit of more than 2/3) is inside the window
        and counts as one update."""
        from tendermint_tpu import trace

        latencies: list[float] = []
        t0 = t = time.perf_counter()
        deadline = t0 + seconds
        while t < deadline:
            walk: list = []
            with trace.span("bench.update", "bench", height=1):
                client = self.new_client()
            self.clients.append((client, walk))
            latencies.append((time.perf_counter() - t) * 1e3)
            for height in self.schedule:
                t = time.perf_counter()
                if t >= deadline:
                    break
                try:
                    with trace.span("bench.update", "bench", height=height):
                        lb = client.verify_light_block_at_height(height)
                    walk.append((height, lb))
                except Exception as e:  # noqa: BLE001 - an update refused wrongly: counted
                    self.errors.append(f"{height}: {type(e).__name__}: {e}")
                latencies.append((time.perf_counter() - t) * 1e3)
            # The finished walk's blocks stay alive only because check()
            # reads them (a client in service prunes its store): out of
            # the collector's sight, or full collections grow with the
            # window. O(1): the generations' lists move.
            gc.freeze()
            t = time.perf_counter()
        return {"ops": len(self.clients) + sum(len(w) for _, w in self.clients),
                "window_s": t - t0, "latencies_ms": latencies, "walks": len(self.clients)}

    # ------------------------------------------------------------- correct

    def _verdicts(self, commit) -> tuple[bool, bool]:
        """The reference's two checks of a non-adjacent step, the
        validator set never changing: more than 1/3 of the trusted set,
        then more than 2/3 of the new one, each stopping once reached."""
        chain = self.chain
        sigs, msgs = chainlib.commit_values(chain, commit)
        trusting, _ = ref.commit_verdict(chain.pubkeys, chain.powers, sigs, msgs, 1, 3, True)
        light, _ = ref.commit_verdict(chain.pubkeys, chain.powers, sigs, msgs, 2, 3, True)
        return trusting, light

    def check(self) -> tuple[list[Check], int, int]:
        chain, rng = self.chain, random.Random(self.seed)
        wrong_hash = wrong_header = wrong_commit = not_stored = 0
        updates = []
        for client, walk in self.clients:
            root = client.store.light_block(1)
            not_stored += root is None
            if root is not None:
                updates.append((1, root))
            for height, lb in walk:
                stored = client.store.light_block(height)
                not_stored += stored is None or stored.signed_header.hash() != lb.signed_header.hash()
                updates.append((height, lb))
        for height, lb in updates:
            wrong_hash += lb.signed_header.commit.block_id.hash != chain.block_hashes[height - 1]
        sample = rng.sample(updates, min(self.params["check_sample"], len(updates)))
        if updates:
            sample.append(updates[-1])
        for height, lb in sample:
            header = lb.signed_header.header
            wrong_header += (header.height != height
                             or ref.header_hash(chainlib.header_values(header))
                             != chain.block_hashes[height - 1])
            wrong_commit += not all(self._verdicts(lb.signed_header.commit))
        refusal = self._refusal(rng)
        checks = [
            Check("headers_differing_from_source", wrong_hash, 0),
            Check("headers_differing_from_reference_hash", wrong_header, 0),
            Check("stored_commits_the_reference_refuses", wrong_commit, 0),
            Check("headers_returned_but_not_stored", not_stored, 0),
            Check("updates_refused_wrongly", len(self.errors), 0),
            Check("refusal_faults", refusal, 0),
        ]
        failed = wrong_hash + wrong_header + wrong_commit + not_stored + len(self.errors) + refusal
        return checks, len(updates) + len(self.errors) + 2, failed

    def _refusal(self, rng) -> int:
        """Twice, the primary serves a header whose commit has one
        signature the curve equation refuses: once in the first half of
        the batch a non-adjacent step checks first
        (VerifyCommitLightTrusting: the validators holding more than 1/3
        of the power), once in the second half of the batch it checks
        next (VerifyCommitLight: more than 2/3) and outside the first
        batch, so that a verification that leaves either half of its
        batches out is seen. Each time the client must raise with that
        verdict and store nothing at the height, and the reference must
        refuse the same commit."""
        from tendermint_tpu.light.verifier import ErrInvalidHeader
        from tendermint_tpu.proto import messages as pb
        from tendermint_tpu.types.light_block import LightBlock

        chain = self.chain
        height = self.schedule[0]
        trusting = chainlib.signing_prefix(chain, 1, 3)
        light = chainlib.signing_prefix(chain, 2, 3)
        faults, self.refusal = 0, []
        for half in (range(trusting // 2), range(max(light // 2, trusting), light)):
            bad_index = rng.choice(half)
            forged = LightBlock.from_proto(pb.LightBlock.decode(self.encoded[height]))
            cs = forged.signed_header.commit.signatures[bad_index]
            cs.signature = chainlib.flip_s(cs.signature)
            accepted = all(self._verdicts(forged.signed_header.commit))
            blocks = dict(self.encoded)
            blocks[height] = forged.to_proto().encode()
            client = self.new_client(blocks)
            raised = None
            try:
                client.verify_light_block_at_height(height)
            except Exception as e:  # noqa: BLE001 - any refusal is recorded by its type
                raised = e
            stored = client.store.light_block(height) is not None
            self.refusal.append({
                "height": height, "bad_index": bad_index, "reference_accepts": accepted,
                "raised": f"{type(raised).__name__}: {str(raised)[:40]}" if raised else None,
                "stored": stored,
            })
            faults += int(accepted) + stored
            faults += not (isinstance(raised, ErrInvalidHeader)
                           and f"wrong signature (#{bad_index})" in str(raised))
        return faults
