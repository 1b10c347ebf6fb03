"""Light client tests (ref: light/verifier_test.go, client_test.go,
detector_test.go)."""

from __future__ import annotations

import pytest

from helpers import make_genesis_doc, make_keys
from test_consensus import fast_params, make_node, wait_for_height
from tendermint_tpu.light import (
    DBLightStore,
    LightClient,
    LocalProvider,
    MemLightStore,
    TrustOptions,
    verify_adjacent,
    verify_non_adjacent,
)
from tendermint_tpu.light.client import SEQUENTIAL, ErrLightClientAttack, LightClientError
from tendermint_tpu.light.verifier import (
    ErrInvalidHeader,
    ErrNewValSetCantBeTrusted,
    ErrOldHeaderExpired,
    validate_trust_level,
)
from tendermint_tpu.store.kv import MemDB
from tendermint_tpu.types.light_block import LightBlock, SignedHeader
from tendermint_tpu.types.validation import Fraction
from tendermint_tpu.utils.tmtime import Time

CHAIN = "light-test-chain"
HOUR_NS = 3600 * 10**9

_chain_cache = {}


def build_chain(n_heights=6):
    """A committed chain + LocalProvider (module-cached: building takes
    seconds and the chain is immutable once built)."""
    if n_heights in _chain_cache:
        return _chain_cache[n_heights]
    keys = make_keys(1)
    gen_doc = make_genesis_doc(keys, CHAIN)
    gen_doc.consensus_params = fast_params()
    node = make_node(keys, 0, gen_doc)
    node.start()
    try:
        assert wait_for_height([node], n_heights, timeout=90)
    finally:
        node.stop()
    provider = LocalProvider(CHAIN, node.block_store, node.block_exec.store)
    _chain_cache[n_heights] = (node, provider)
    return node, provider


def now_after(provider) -> Time:
    latest = provider.light_block(0)
    return Time.from_unix_ns(latest.signed_header.header.time.unix_ns() + 10**9)


def test_validate_trust_level():
    validate_trust_level(Fraction(1, 3))
    validate_trust_level(Fraction(2, 3))
    validate_trust_level(Fraction(1, 1))
    for bad in (Fraction(1, 4), Fraction(4, 3), Fraction(0, 1)):
        with pytest.raises(ValueError):
            validate_trust_level(bad)


def test_verify_adjacent_ok():
    node, provider = build_chain()
    lb1 = provider.light_block(1)
    lb2 = provider.light_block(2)
    verify_adjacent(
        CHAIN, lb1.signed_header, lb2.signed_header, lb2.validator_set,
        HOUR_NS, now_after(provider), 10 * 10**9,
    )


def test_verify_adjacent_rejects_expired_trust():
    node, provider = build_chain()
    lb1 = provider.light_block(1)
    lb2 = provider.light_block(2)
    with pytest.raises(ErrOldHeaderExpired):
        verify_adjacent(
            CHAIN, lb1.signed_header, lb2.signed_header, lb2.validator_set,
            1, now_after(provider), 10 * 10**9,  # 1ns trusting period
        )


def test_verify_non_adjacent_ok():
    node, provider = build_chain()
    lb1 = provider.light_block(1)
    lb4 = provider.light_block(4)
    verify_non_adjacent(
        CHAIN, lb1.signed_header, lb1.validator_set, lb4.signed_header, lb4.validator_set,
        HOUR_NS, now_after(provider), 10 * 10**9,
    )


def test_verify_rejects_tampered_header():
    node, provider = build_chain()
    lb1 = provider.light_block(1)
    lb2 = provider.light_block(2)
    import copy

    evil = copy.deepcopy(lb2)
    evil.signed_header.header.app_hash = b"\xec" * 32
    with pytest.raises(Exception):
        verify_adjacent(
            CHAIN, lb1.signed_header, evil.signed_header, evil.validator_set,
            HOUR_NS, now_after(provider), 10 * 10**9,
        )


def _trust_options(provider, height=1):
    lb = provider.light_block(height)
    return TrustOptions(period_ns=24 * HOUR_NS, height=height, hash=lb.signed_header.hash())


def test_client_skipping_verification():
    node, provider = build_chain()
    target = node.block_store.height()
    client = LightClient(
        CHAIN, _trust_options(provider), provider, clock=lambda: now_after(provider)
    )
    lb = client.verify_light_block_at_height(target)
    assert lb.height == target
    assert client.latest_trusted().height == target


def test_client_sequential_verification():
    node, provider = build_chain()
    target = node.block_store.height()
    client = LightClient(
        CHAIN, _trust_options(provider), provider,
        verification_mode=SEQUENTIAL, clock=lambda: now_after(provider),
    )
    lb = client.verify_light_block_at_height(target)
    assert lb.height == target
    # sequential stores every intermediate header
    for h in range(1, target + 1):
        assert client.trusted_light_block(h) is not None


def test_client_backwards_verification():
    node, provider = build_chain()
    target = node.block_store.height()
    client = LightClient(
        CHAIN,
        TrustOptions(period_ns=24 * HOUR_NS, height=target, hash=provider.light_block(target).signed_header.hash()),
        provider,
        clock=lambda: now_after(provider),
    )
    lb = client.verify_light_block_at_height(1)
    assert lb.height == 1
    assert lb.signed_header.hash() == provider.light_block(1).signed_header.hash()


def test_client_detects_forged_witness():
    """A witness serving a diverging header at the verified height
    triggers attack evidence (ref: detector_test.go)."""
    import copy

    node, provider = build_chain()
    target = node.block_store.height()

    class EvilProvider(LocalProvider):
        def light_block(self, height):
            lb = super().light_block(height)
            evil = copy.deepcopy(lb)
            evil.signed_header.header.app_hash = b"\x66" * 32
            return evil

    evil = EvilProvider(CHAIN, node.block_store, node.block_exec.store, name="evil-witness")
    client = LightClient(
        CHAIN, _trust_options(provider), provider, witnesses=[evil],
        clock=lambda: now_after(provider),
    )
    with pytest.raises(ErrLightClientAttack):
        client.verify_light_block_at_height(target)
    assert client.latest_attack_evidence is not None
    assert provider.evidence, "evidence must be reported to providers"


def test_client_persists_to_db_store():
    node, provider = build_chain()
    target = node.block_store.height()
    db = MemDB()
    client = LightClient(
        CHAIN, _trust_options(provider), provider,
        trusted_store=DBLightStore(db), clock=lambda: now_after(provider),
    )
    client.verify_light_block_at_height(target)
    # second client restores trust from the same DB without refetching root
    client2 = LightClient(
        CHAIN, _trust_options(provider), provider,
        trusted_store=DBLightStore(db), clock=lambda: now_after(provider),
    )
    assert client2.latest_trusted().height == target


def test_client_bisection_on_trust_failure(monkeypatch):
    """When a direct jump fails the trust-fraction check, the client
    bisects to the midpoint and retries (ref: client.go:647
    verifySkipping). Simulated by rejecting jumps of more than 2
    heights, as a rotated validator set would."""
    node, provider = build_chain()
    target = node.block_store.height()
    from tendermint_tpu.light import client as client_mod
    from tendermint_tpu.light import verifier as vf

    real = vf.verify_non_adjacent
    jumps = []

    def limited(chain_id, th, tv, uh, uv, *a, **k):
        jumps.append((th.header.height, uh.header.height))
        if uh.header.height - th.header.height > 2:
            raise vf.ErrNewValSetCantBeTrusted("simulated validator rotation")
        return real(chain_id, th, tv, uh, uv, *a, **k)

    monkeypatch.setattr(client_mod.vf, "verify_non_adjacent", limited)
    client = LightClient(
        CHAIN, _trust_options(provider), provider, clock=lambda: now_after(provider)
    )
    lb = client.verify_light_block_at_height(target)
    assert lb.height == target
    assert any(b - a > 2 for a, b in jumps), "a long jump must have been attempted"
    # bisection must have fetched midpoints: some non-adjacent jump of
    # <=2 heights eventually succeeded
    assert any(b - a <= 2 for a, b in jumps), f"no bisected jump seen: {jumps}"


def test_client_update_follows_head():
    node, provider = build_chain()
    client = LightClient(
        CHAIN, _trust_options(provider), provider, clock=lambda: now_after(provider)
    )
    lb = client.update()
    assert lb.height == node.block_store.height()


def test_update_noop_and_conflict_at_trusted_height():
    """Update() against a primary whose head equals our trusted height:
    same header -> no-op returning the trusted block; DIFFERENT header
    at that height -> conflict error, never a silent overwrite
    (ref: client.go Update same-height hash mismatch)."""
    node, provider = build_chain()
    target = node.block_store.height()
    client = LightClient(
        CHAIN, _trust_options(provider), provider, clock=lambda: now_after(provider)
    )
    client.verify_light_block_at_height(target)

    got = client.update()
    assert got is not None and got.height == target  # no-op: already at head

    # a primary that rewrites history at our trusted height
    forged = provider.light_block(target)
    import copy

    forged = copy.deepcopy(forged)
    forged.signed_header.header.app_hash = b"\x13" * 32
    real_lb = provider.light_block

    def lying(h):
        if h in (0, target):
            return forged
        return real_lb(h)

    provider.light_block = lying
    try:
        with pytest.raises(LightClientError, match="conflicting header"):
            client.update()
    finally:
        provider.light_block = real_lb


def test_verify_below_any_trusted_state_rejected():
    """Skipping mode holds only the trust root + verified heads; asking
    for a height BELOW every trusted state must error (backwards
    verification is its own entry point, ref client.go:497)."""
    node, provider = build_chain()
    target = node.block_store.height()
    client = LightClient(
        CHAIN,
        TrustOptions(
            period_ns=24 * HOUR_NS,
            height=target,
            hash=provider.light_block(target).signed_header.hash(),
        ),
        provider,
        clock=lambda: now_after(provider),
    )
    client.verify_light_block_at_height(target)
    with pytest.raises(LightClientError, match="no trusted state below"):
        client._verify_light_block(provider.light_block(1), now_after(provider))


def test_witness_down_is_skipped_not_fatal():
    """A witness that errors during divergence detection is skipped
    (the reference drops it after retries); detection still passes via
    the remaining honest witness."""
    node, provider = build_chain()
    target = node.block_store.height()

    class DownProvider:
        def light_block(self, height):
            raise ConnectionError("witness down")

    client = LightClient(
        CHAIN, _trust_options(provider), provider,
        witnesses=[DownProvider(), provider],
        clock=lambda: now_after(provider),
    )
    lb = client.verify_light_block_at_height(target)
    assert lb.height == target


def test_all_witnesses_down_fails_cross_reference():
    """Eclipse defense (ref: detector.go ErrFailedHeaderCrossReferencing):
    when EVERY configured witness is unreachable, verification must fail
    rather than trust the primary with zero cross-checks."""
    node, provider = build_chain()
    target = node.block_store.height()

    class DownProvider:
        def light_block(self, height):
            raise ConnectionError("witness down")

    client = LightClient(
        CHAIN, _trust_options(provider), provider,
        witnesses=[DownProvider(), DownProvider()],
        clock=lambda: now_after(provider),
    )
    with pytest.raises(LightClientError, match="cross-reference"):
        client.verify_light_block_at_height(target)


def test_lagging_witness_retried_not_fatal():
    """A witness that merely LAGS the head (ErrLightBlockNotFound, not
    a network failure) is retried with backoff and verification
    succeeds once it catches up — head-of-chain updates must not trip
    the zero-cross-reference failure on honest setups."""
    from tendermint_tpu.light.provider import ErrLightBlockNotFound

    node, provider = build_chain()
    target = node.block_store.height()

    class LaggingProvider:
        def __init__(self):
            self.calls = 0

        def light_block(self, height):
            self.calls += 1
            if self.calls <= 2:
                raise ErrLightBlockNotFound(f"no light block at height {height}")
            return provider.light_block(height)

    lagging = LaggingProvider()
    client = LightClient(
        CHAIN, _trust_options(provider), provider, witnesses=[lagging],
        clock=lambda: now_after(provider),
    )
    lb = client.verify_light_block_at_height(target)
    assert lb.height == target
    assert lagging.calls >= 3, "witness was not retried"


# -- a light block decodes what is read (types/light_block.py) -----------------
#
# Providers that hold every block as its wire encoding and decode it on
# each fetch, as a client over RPC does: the benchmark's own
# (benchmark/drivers/light.py), so what the light cells run is what is held.


def _encoded_providers(provider, heights, witness_blocks=None):
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmark.drivers.light import make_provider_class

    cls = make_provider_class()
    blocks = {h: provider.light_block(h).to_proto().encode() for h in heights}
    return cls(CHAIN, blocks, "primary"), cls(CHAIN, {**blocks, **(witness_blocks or {})}, "witness0"), blocks


def _with_garbage_parts(raw: bytes) -> bytes:
    """The same header, framing intact, and bytes that are no message
    where the commit and the validator set were."""
    from test_light_block_lazy import GARBAGE, decoded, with_parts

    return with_parts(decoded(raw), commit=GARBAGE, validator_set=GARBAGE)


def _forked(provider, height: int, app_hash: bytes) -> bytes:
    lb = provider.light_block(height)
    lb.signed_header.header.app_hash = app_hash
    return lb.to_proto().encode()


def _part_samples():
    from tendermint_tpu.metrics import light_metrics

    return {(labels["part"], labels["event"]): value
            for _, labels, value in light_metrics().block_parts.samples()}


def test_an_update_reads_the_primarys_parts_and_leaves_the_witnesss_bytes():
    from tendermint_tpu import trace

    node, provider = build_chain()
    target = node.block_store.height()
    primary, witness, _ = _encoded_providers(provider, [1, target])
    client = LightClient(CHAIN, _trust_options(provider), primary, witnesses=[witness],
                         clock=lambda: now_after(provider))
    before = _part_samples()
    was = trace.enabled()
    trace.set_enabled(True)
    trace.clear()
    try:
        lb = client.verify_light_block_at_height(target)
        events = [ev for ev in trace.export()["traceEvents"] if ev.get("ph") == "X"]
    finally:
        trace.set_enabled(was)
        trace.clear()
    assert lb.height == target and client.store.light_block(target) is lb
    grown = {k: v - before.get(k, 0.0) for k, v in _part_samples().items() if v != before.get(k, 0.0)}
    assert grown == {("commit", "deferred"): 2, ("validator_set", "deferred"): 2,
                     ("commit", "read"): 1, ("validator_set", "read"): 1}
    fetches = {ev["args"]["span"]: ev["args"] for ev in events if ev["name"] == "light.fetch"}
    assert sorted(a["purpose"] for a in fetches.values()) == ["target", "witness"]
    parts = [ev["args"] for ev in events if ev["name"] == "light.decode_part"]
    assert sorted(a["part"] for a in parts) == ["commit", "validator_set"]
    assert all(fetches[a["parent"]]["purpose"] == "target" for a in parts)
    # the witness's copy was compared and let go with both parts still bytes
    divergence = next(ev["args"] for ev in events if ev["name"] == "light.detect_divergence")
    assert divergence["cross_referenced"] == 1


def test_a_witness_with_another_header_still_yields_evidence_that_validates():
    node, provider = build_chain()
    target = node.block_store.height()
    primary, witness, _ = _encoded_providers(
        provider, [1, target], {target: _forked(provider, target, b"\x66" * 32)})
    client = LightClient(CHAIN, _trust_options(provider), primary, witnesses=[witness],
                         clock=lambda: now_after(provider))
    with pytest.raises(ErrLightClientAttack):
        client.verify_light_block_at_height(target)
    ev = client.latest_attack_evidence
    assert ev is not None and ev.conflicting_block.signed_header.header.app_hash == b"\x66" * 32
    assert len(ev.conflicting_block.signed_header.commit.signatures) == ev.conflicting_block.validator_set.size()
    # its parts were read from the witness's bytes; a forged header no longer is what the commit signs
    with pytest.raises(ValueError, match="commit signs block"):
        ev.validate_basic()
    ev.conflicting_block.validator_set.validate_basic()
    ev.conflicting_block.signed_header.commit.validate_basic()
    assert client.store.light_block(target) is None


def test_a_witness_with_a_conflicting_sound_block_yields_evidence_that_validates():
    """The witness answers with a sound light block of another header
    (the chain's own, one height down)."""
    node, provider = build_chain()
    target = node.block_store.height()
    lb = provider.light_block(target)
    other = provider.light_block(target - 1)  # sound in itself, another header
    other_raw = other.to_proto().encode()
    primary, witness, _ = _encoded_providers(provider, [1, target], {target: other_raw})
    client = LightClient(CHAIN, _trust_options(provider), primary, witnesses=[witness],
                         clock=lambda: now_after(provider))
    with pytest.raises(ErrLightClientAttack):
        client.verify_light_block_at_height(target)
    ev = client.latest_attack_evidence
    assert ev.conflicting_block.signed_header.hash() == other.signed_header.hash() != lb.signed_header.hash()
    ev.conflicting_block.validate_basic(CHAIN)  # read from the witness's bytes, whole and sound
    assert ev.total_voting_power > 0 and provider.evidence is not None


def test_a_witness_whose_header_matches_is_cross_referenced_whatever_its_parts_hold():
    node, provider = build_chain()
    target = node.block_store.height()
    raw = provider.light_block(target).to_proto().encode()
    primary, witness, _ = _encoded_providers(provider, [1, target], {target: _with_garbage_parts(raw)})
    with pytest.raises(ValueError):
        witness.light_block(target).validator_set
    client = LightClient(CHAIN, _trust_options(provider), primary, witnesses=[witness],
                         clock=lambda: now_after(provider))
    assert client.verify_light_block_at_height(target).height == target
    assert client.latest_attack_evidence is None


@pytest.mark.parametrize("honest_witness", [False, True])
def test_a_diverging_witness_whose_parts_are_garbage_gave_no_usable_block(honest_witness):
    node, provider = build_chain()
    target = node.block_store.height()
    forged = _with_garbage_parts(_forked(provider, target, b"\x66" * 32))
    primary, witness, _ = _encoded_providers(provider, [1, target], {target: forged})
    witnesses = [witness] + ([provider] if honest_witness else [])
    client = LightClient(CHAIN, _trust_options(provider), primary, witnesses=witnesses,
                         clock=lambda: now_after(provider))
    if honest_witness:
        assert client.verify_light_block_at_height(target).height == target
    else:
        # as when every witness is down: no cross-check, nothing trusted, and no ValueError out of the update
        with pytest.raises(LightClientError, match="cross-reference"):
            client.verify_light_block_at_height(target)
        assert client.store.light_block(target) is None
    assert client.latest_attack_evidence is None
