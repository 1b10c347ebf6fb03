"""Closed loop, one light client that has fallen behind a chain whose
validator set rotates: every update asks for one height far above the
trusted one, and where too few of the trusted set still sign there the
client bisects: the jump refused, a pivot fetched between the two, a
header stored on the way.

`benchmark.drivers.light`'s loop, clients and providers (a light block
is its wire encoding, made in set-up, decoded on every fetch), over
`benchmark/chain_churn.py`'s chain, each height served with its own
set. An operation is a target returned (a client's trust root among
them), never a pivot, so a client that bisects more is not faster.

Parameters (`benchmark/traffic/<mix>.json`), beside `light`'s
`witnesses`, `trusting_period_s`, `check_sample`:
    spans   heights between one target and the next; the schedule is this list
            repeated while it fits below the chain's end, shuffled by the seed

`correct` holds the client to the guarantee bisection exists to keep,
against `benchmark/reference_churn.py`: every header in a client's
store, pivots too, carries the scheduled set; every pair of
neighbouring heights in a store is a link the reference's own tally
allows; and a forged commit is refused in a direct step and in a pivot.
"""

from __future__ import annotations

import random
import time

from benchmark import chain as chainlib
from benchmark import chain_churn
from benchmark import reference as ref
from benchmark import reference_churn as refc
from benchmark.drivers import Check, light


def stored_blocks(client) -> list:
    """Every light block of a client's store, lowest height first."""
    out, lb = [], client.store.latest_light_block()
    while lb is not None:
        out.append(lb)
        lb = client.store.light_block_before(lb.height)
    return out[::-1]


class Traffic(light.Traffic):
    def build(self) -> None:
        from tendermint_tpu.types.light_block import LightBlock, SignedHeader

        config = self.config
        self.chain = chain = chain_churn.build(config, self.seed)
        for h in range(1, chain.height + 1):
            lb = LightBlock(SignedHeader(chain.block_store.load_block_meta(h).header,
                                         chain.block_store.load_seen_commit(h)),
                            chain.state_store.load_validators(h))
            self.encoded[h] = lb.to_proto().encode()
        # the reference's side: keys from the seed, sets from the rotation rule
        per_block = config["rotation"]["validators_per_block"]
        keys = [ref.public_key(s) for s in chainlib.key_seeds(
            self.seed, config["validators"] + config["blocks"] * per_block)]
        self.sets = refc.Schedule(keys, config["validators"], config["voting_power"], per_block)
        spans, steps, at = self.params["spans"], [], 1
        while at + spans[len(steps) % len(spans)] <= chain.height:
            steps.append(spans[len(steps) % len(spans)])
            at += steps[-1]
        random.Random(self.seed).shuffle(steps)
        at = 1
        for s in steps:
            at += s
            self.schedule.append(at)
        self.provider_class = light.make_provider_class()
        self.now_ns = chain.times_ns[-1] + 10**9

    def warm_up(self) -> None:
        """Two walks, each by a fresh client: the first loads every
        program and fills the pubkey cache (every key a batch looks up
        misses once), the second runs as the window will. Update by
        update, so that a miss's price can be read where the programs
        are already loaded: the later updates of the first walk against
        the same updates of the second."""
        from tendermint_tpu.metrics import engine_metrics

        def counters():
            m = engine_metrics()
            missed = sum(value for _, _, value in m.pk_cache_missed_rows.samples())
            fills = sum(value for _, labels, value in m.kernel_launches.samples()
                        if labels.get("kernel") == "pk_table_build")
            return missed, fills

        for name in ("cold", "warm"):
            updates, client, t0 = [], None, time.perf_counter()
            for height in [1] + self.schedule:
                before, t = counters(), time.perf_counter()
                if client is None:
                    client = self.new_client()  # its trust root, height 1
                else:
                    client.verify_light_block_at_height(height)
                missed, fills = (int(b - a) for a, b in zip(before, counters()))
                updates.append([height, round((time.perf_counter() - t) * 1e3, 3), missed, fills])
            print(f"warm-up walk ({name} pubkey cache): {time.perf_counter() - t0:.3f}s; "
                  f"[height, ms, keys missed, fills] an update: {updates}", flush=True)

    # ------------------------------------------------------------- correct

    def _trusting_walk(self, lower: int, upper: int, signed: list[bool] | None = None):
        """The reference's walk of the trusting check for a jump between
        two heights of the schedule (everyone signing, unless told):
        (more than 1/3 of the lower set reached, the commit rows verified)."""
        signing = self.sets.set_at(upper)
        return refc.trusting_rows(self.sets.set_at(lower), [pk for pk, _ in signing],
                                  signed or [True] * len(signing))

    def _jump_trusted(self, lower: int, upper: int) -> bool:
        return self._trusting_walk(lower, upper)[0]

    def _link_allowed(self, lower, upper) -> bool:
        """The reference's power tally, no signature verified: may a
        client that trusts `lower` store `upper` next? A trust root has
        no lower neighbour and needs more than 2/3 of its own set."""
        height = upper.height
        signing = self.sets.set_at(height)
        signed = [cs.block_id_flag == 2 for cs in upper.signed_header.commit.signatures]
        if len(signed) != len(signing) or not refc.light_rows(signing, signed)[0]:
            return False
        if lower is None:
            return True
        if height == lower.height + 1:
            return (self.sets.hash_at(height) == lower.signed_header.header.next_validators_hash
                    == upper.signed_header.header.validators_hash)
        return self._trusting_walk(lower.height, height, signed)[0]

    def _link_verifies(self, lower, upper) -> bool:
        """The reference's verdicts on the signatures both checks of the
        step reach (the 2/3 check alone for a root or an adjacent step)."""
        signing = self.sets.set_at(upper.height)
        sigs, msgs = chainlib.commit_values(self.chain, upper.signed_header.commit)
        skipping = lower is not None and upper.height > lower.height + 1
        trusted = self.sets.set_at(lower.height) if skipping else signing
        trusting, own = refc.step_verdicts(trusted, signing, sigs, msgs)
        return own and (trusting or not skipping)

    def _sets_differ(self, lb) -> bool:
        header, h = lb.signed_header.header, lb.height
        carried = [(v.pub_key.bytes(), v.voting_power) for v in lb.validator_set.validators]
        # the set carried, value for value in order: equal sets, equal reference hashes
        return not (header.validators_hash == self.sets.hash_at(h)
                    and header.next_validators_hash == self.sets.hash_at(h + 1)
                    and carried == self.sets.set_at(h))

    def check(self) -> tuple[list[Check], int, int]:
        chain, rng = self.chain, random.Random(self.seed)
        wrong_hash = wrong_header = wrong_sets = wrong_links = not_stored = 0
        updates, links, examined = [], [], {}
        for client, walk in self.clients:
            root = client.store.light_block(1)
            not_stored += root is None
            if root is not None:
                updates.append((1, root))
            for height, lb in walk:
                stored = client.store.light_block(height)
                not_stored += stored is None or stored.signed_header.hash() != lb.signed_header.hash()
                updates.append((height, lb))
            blocks = stored_blocks(client)
            for lower, upper in zip([None] + blocks, blocks):
                examined[id(upper)] = upper
                wrong_sets += self._sets_differ(upper)
                wrong_links += not self._link_allowed(lower, upper)
                links.append((lower, upper))
        examined.update((id(lb), lb) for _, lb in updates)
        for lb in examined.values():  # every header returned or stored on the way, once
            wrong_hash += lb.signed_header.commit.block_id.hash != chain.block_hashes[lb.height - 1]
        sample = rng.sample(links, min(self.params["check_sample"], len(links)))
        if links:
            sample.append(links[-1])
        for lower, upper in sample:
            header = upper.signed_header.header
            wrong_header += (ref.header_hash(chainlib.header_values(header))
                             != chain.block_hashes[upper.height - 1])
            wrong_links += not self._link_verifies(lower, upper)
        refusal = self._refusal(rng)
        checks = [
            Check("headers_differing_from_source", wrong_hash, 0),
            Check("headers_differing_from_reference_hash", wrong_header, 0),
            Check("headers_returned_but_not_stored", not_stored, 0),
            Check("updates_refused_wrongly", len(self.errors), 0),
            Check("validator_sets_differing_from_schedule", wrong_sets, 0),
            Check("trust_links_the_reference_refuses", wrong_links, 0),
            Check("refusal_faults", refusal, 0),
        ]
        failed = sum(c.value for c in checks)
        return checks, len(updates) + len(self.errors) + 3, failed

    def _refusal(self, rng) -> int:
        """Three times a fresh client's primary serves one commit with
        one signature only the curve equation refuses, the row chosen
        from the reference's own walk of that step: in the first half
        of the batch a direct step's trusting check verifies; in the
        second half of its 2/3 batch and outside the trusting batch;
        and in the second half of the trusting batch of a PIVOT, the
        header a bisecting update verifies on the way to its target.
        Each time the client must raise `ErrInvalidHeader` with that
        row's verdict and store nothing above its trust root, and the
        reference must refuse the same commit."""
        from tendermint_tpu.light.verifier import ErrInvalidHeader
        from tendermint_tpu.proto import messages as pb
        from tendermint_tpu.types.light_block import LightBlock

        spans = sorted(set(self.params["spans"]))
        direct = 1 + rng.choice([s for s in spans if self._jump_trusted(1, 1 + s)])
        far = 1 + next(s for s in spans if not self._jump_trusted(1, 1 + s)
                       and self._jump_trusted(1, (2 + s) // 2))
        pivot = (1 + far) // 2

        trusting = self._trusting_walk(1, direct)[1]
        own = refc.light_rows(self.sets.set_at(direct), [True] * len(self.sets.set_at(direct)))[1]
        pivot_trusting = self._trusting_walk(1, pivot)[1]
        probes = [
            (direct, direct, trusting[: len(trusting) // 2]),
            (direct, direct, [r for r in own[len(own) // 2:] if r not in trusting]),
            (far, pivot, pivot_trusting[len(pivot_trusting) // 2:]),
        ]
        faults, self.refusal = 0, []
        for target, forged_height, candidates in probes:
            bad_index = rng.choice(candidates)
            forged = LightBlock.from_proto(pb.LightBlock.decode(self.encoded[forged_height]))
            cs = forged.signed_header.commit.signatures[bad_index]
            cs.signature = chainlib.flip_s(cs.signature)
            sigs, msgs = chainlib.commit_values(self.chain, forged.signed_header.commit)
            accepted = all(refc.step_verdicts(self.sets.set_at(1), self.sets.set_at(forged_height),
                                              sigs, msgs))
            blocks = dict(self.encoded)
            blocks[forged_height] = forged.to_proto().encode()
            client = self.new_client(blocks)
            raised = None
            try:
                client.verify_light_block_at_height(target)
            except Exception as e:  # noqa: BLE001 - any refusal is recorded by its type
                raised = e
            stored = client.store.latest_light_block().height > 1
            self.refusal.append({
                "target": target, "forged": forged_height, "bad_index": bad_index,
                "reference_accepts": accepted,
                "raised": f"{type(raised).__name__}: {str(raised)[:40]}" if raised else None,
                "stored": stored,
            })
            faults += int(accepted) + stored
            faults += not (isinstance(raised, ErrInvalidHeader)
                           and f"wrong signature (#{bad_index})" in str(raised))
        return faults
