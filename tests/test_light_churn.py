"""A light client that has fallen behind a chain whose validator set
rotates (one validator a block), held to the benchmark's plain
reference (`benchmark/reference_churn.py`): the builder's sets against
the rotation rule, bisection exactly where the reference's tally
refuses a jump, every link a client stores, the refusal probes, under
each route the engine can give the batches; with them the pubkey
cache's fill at the launch bucket, `light.fetch`'s `purpose` and the
light client's two counters. What `light-150-churn` runs on the chip,
at a size that compiles here.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import tendermint_tpu.crypto.ed25519 as ed
from benchmark import reference_churn as refc
from benchmark.drivers.light_catchup import Traffic, stored_blocks
from tendermint_tpu import devobs, trace
from tendermint_tpu.metrics import light_metrics
from tendermint_tpu.ops import verify as V

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_light_routes import ROUTES, _grown, _samples  # noqa: E402

# 24 equal validators: more than 1/3 of a trusted set is 9 of them, so a
# jump is trusted while at most 15 have left: over 15 heights, 16 from
# height 1 (a change in block H acts from H + 2). Spans 4 and 9 are one
# step; 20 is one refused jump, one pivot, two steps; 42 three, three, four.
CONFIG = {"validators": 24, "voting_power": 10, "txs_per_block": 2, "chain_id": "chain-churn",
          "blocks": 80, "rotation": {"validators_per_block": 1}}
PARAMS = {"spans": [4, 9, 20, 42], "witnesses": 1, "trusting_period_s": 1209600,
          "check_sample": 4}
SEED = 2147483659  # past 31 bits, as the driver's seeds are


@pytest.fixture(scope="module")
def traffic():
    t = Traffic(CONFIG, PARAMS, SEED)
    t.build()
    return t


@pytest.fixture
def host_route(monkeypatch):
    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", 100)
    monkeypatch.setattr(ed, "MSM_BATCH_CUTOVER", 100)


# ------------------------------------------------- the builder against the rule


@pytest.mark.parametrize("what", ["sets_as_built", "validators_hash", "next_validators_hash",
                                  "load_validators"])
def test_the_set_at_every_height_is_the_reference_schedules(traffic, what):
    chain, sets = traffic.chain, traffic.sets
    for h in range(1, chain.height + 1):
        header = chain.block_store.load_block_meta(h).header
        got = {
            "sets_as_built": lambda: refc.validator_set_hash(chain.sets[h - 1]),
            "validators_hash": lambda: header.validators_hash,
            "next_validators_hash": lambda: header.next_validators_hash,
            "load_validators": lambda: chain.state_store.load_validators(h).hash(),
        }[what]()
        assert got == sets.hash_at(h + 1 if what == "next_validators_hash" else h), h
    # the rule itself: nothing moves before height 3, then one key a height
    assert sets.set_at(1) == sets.set_at(2) != sets.set_at(3)
    assert len({pk for pk, _ in sets.set_at(3)} & {pk for pk, _ in sets.set_at(19)}) == 8


# ------------------------------------------------- bisection where the tally says


def _expected_path(traffic, lower: int, upper: int) -> tuple[list[int], int]:
    """(heights verified on the way from `lower` to `upper`, jumps
    refused), by the reference's tally and the client's midpoint rule."""
    verified, refused, pending = [lower], 0, [upper]
    while pending:
        if pending[-1] == verified[-1] + 1 or traffic._jump_trusted(verified[-1], pending[-1]):
            verified.append(pending.pop())
        else:
            refused += 1
            pending.append((verified[-1] + pending[-1]) // 2)
    return verified[1:], refused


@pytest.mark.parametrize("start,span,refused", [
    (1, 16, 0), (1, 17, 1),  # from the root the limit is 16 heights
    (10, 15, 0), (10, 16, 1),  # above it 15
    (10, 30, 1), (10, 31, 2), (10, 32, 3), (1, 42, 3), (30, 1, 0),
])
def test_the_client_bisects_exactly_where_the_reference_refuses_the_jump(
        traffic, host_route, start, span, refused):
    client = traffic.new_client()
    if start > 1:
        client.verify_light_block_at_height(start)
    held = {lb.height for lb in stored_blocks(client)}
    before = _samples(light_metrics().verify_steps, "outcome")
    pivots = _samples(light_metrics().fetches, "purpose").get("pivot", 0.0)
    target = start + span
    client.verify_light_block_at_height(target)
    path, want_refused = _expected_path(traffic, start, target)
    assert want_refused == refused
    assert [lb.height for lb in stored_blocks(client)] == sorted(held | set(path))
    grown = _grown(before, _samples(light_metrics().verify_steps, "outcome"))
    assert grown == {k: v for k, v in (("ok", len(path)), ("bisect", refused)) if v}
    assert _samples(light_metrics().fetches, "purpose").get("pivot", 0.0) - pivots == refused


# ------------------------------------------------- a walk under each route


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_a_catch_up_walk_under_each_route_passes_the_reference(traffic, monkeypatch, route):
    """The driver's own `check()` over one walk: every header stored,
    pivots among them, carries the scheduled set; every stored link is
    one the reference's tally and its signature verdicts allow; the
    three refusal probes (a direct step's trusting batch, its 2/3 batch
    outside it, a pivot's commit) are refused with the row's verdict."""
    device, msm, pk_cache, _, _ = ROUTES[route]
    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", device)
    monkeypatch.setattr(ed, "MSM_BATCH_CUTOVER", msm)
    monkeypatch.setenv("TM_TPU_PK_CACHE", pk_cache)
    monkeypatch.setattr(traffic, "clients", [])
    monkeypatch.setattr(traffic, "errors", [])
    client = traffic.new_client()
    walk = [(h, client.verify_light_block_at_height(h)) for h in traffic.schedule]
    traffic.clients.append((client, walk))
    assert len(stored_blocks(client)) == 1 + len(traffic.schedule) + 4  # a pivot a refused jump
    checks, attempted, failed = traffic.check()
    assert {c.name: c.value for c in checks} == {
        "headers_differing_from_source": 0, "headers_differing_from_reference_hash": 0,
        "headers_returned_but_not_stored": 0, "updates_refused_wrongly": 0,
        "validator_sets_differing_from_schedule": 0, "trust_links_the_reference_refuses": 0,
        "refusal_faults": 0}
    assert failed == 0 and attempted == len(traffic.schedule) + 1 + 3
    assert [r["forged"] != r["target"] for r in traffic.refusal] == [False, False, True]


def test_a_client_whose_trusting_check_passes_stores_a_link_the_reference_refuses(
        traffic, host_route, monkeypatch):
    from tendermint_tpu.light import verifier

    monkeypatch.setattr(verifier, "verify_commit_light_trusting", lambda *a, **k: None)
    monkeypatch.setattr(traffic, "clients", [])
    monkeypatch.setattr(traffic, "errors", [])
    client = traffic.new_client()
    traffic.clients.append((client, [(43, client.verify_light_block_at_height(43))]))
    assert [lb.height for lb in stored_blocks(client)] == [1, 43]
    values = {c.name: c.value for c in traffic.check()[0]}
    assert values["trust_links_the_reference_refuses"] >= 1
    assert values["validator_sets_differing_from_schedule"] == 0


# ------------------------------------------------- the span's purpose, the counters


def test_every_fetch_says_what_it_is_for_and_both_counters_count(traffic, host_route):
    m = light_metrics()
    fetched = _samples(m.fetches, "purpose")
    steps = _samples(m.verify_steps, "outcome")
    was = trace.enabled()
    trace.set_enabled(True)
    trace.clear()
    try:
        client = traffic.new_client()
        client.verify_light_block_at_height(21)  # one refused jump, one pivot (11), two steps
        events = [ev for ev in trace.export()["traceEvents"] if ev.get("ph") == "X"]
    finally:
        trace.set_enabled(was)
        trace.clear()
    fetches = [(ev["args"]["purpose"], ev["args"]["provider"], ev["args"]["height"])
               for ev in events if ev["name"] == "light.fetch"]
    assert sorted(fetches) == [("pivot", "primary", 11), ("target", "primary", 1),
                               ("target", "primary", 21), ("witness", "witness", 21)]
    assert sorted(ev["args"]["outcome"] for ev in events if ev["name"] == "light.verify_step") \
        == ["bisect", "ok", "ok"]
    assert _grown(fetched, _samples(m.fetches, "purpose")) == {"target": 2, "pivot": 1, "witness": 1}
    assert _grown(steps, _samples(m.verify_steps, "outcome")) == {"ok": 2, "bisect": 1}


@pytest.mark.parametrize("route", ["host", "bitmap_cached"])
def test_a_trust_step_is_one_launch_and_a_refused_jump_none(traffic, monkeypatch, route):
    """Height 21 from a fresh client: the trust root (one check, one
    job), a jump the trusting tally refuses (nothing reaches the
    engine), then two steps, each both of its batches in one group."""
    device, msm, pk_cache, path, _ = ROUTES[route]
    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", device)
    monkeypatch.setattr(ed, "MSM_BATCH_CUTOVER", msm)
    monkeypatch.setenv("TM_TPU_PK_CACHE", pk_cache)
    together = _samples(V._engine_metrics().jobs_submitted_together, "plane")
    was = trace.enabled()
    trace.set_enabled(True)
    trace.clear()
    try:
        traffic.new_client().verify_light_block_at_height(21)
        events = [ev for ev in trace.export()["traceEvents"] if ev.get("ph") == "X"]
    finally:
        trace.set_enabled(was)
        trace.clear()

    def inside(step, name):
        return [ev["args"] for ev in events if ev["name"] == name
                and step["ts"] <= ev["ts"] <= step["ts"] + step["dur"]]

    steps = [ev for ev in events if ev["name"] == "light.verify_step"]
    assert [st["args"]["outcome"] for st in steps] == ["bisect", "ok", "ok"]
    refused, *succeeded = steps
    assert len(inside(refused, "verify.commit_walk")) == 1
    for name in ("verify.commit_dispatch", "engine.submit", "engine.dispatch"):
        assert inside(refused, name) == []
    for step in succeeded:
        assert len(inside(step, "verify.commit_walk")) == 2
        (dispatch,) = inside(step, "verify.commit_dispatch")
        assert dispatch["jobs"] == 2
        assert [a["together"] for a in inside(step, "engine.submit")] == [2, 2]
        (launch,) = inside(step, "engine.dispatch")
        assert (launch["jobs"], launch["path"]) == (2, path)
        assert launch["rows"] == dispatch["nsigs"] == sum(
            a["nsigs"] for a in inside(step, "verify.commit_collect"))
    # the root's one check is the only other group, and it came alone
    assert sorted(ev["args"]["jobs"] for ev in events if ev["name"] == "engine.dispatch") == [1, 2, 2]
    assert _grown(together, _samples(V._engine_metrics().jobs_submitted_together, "plane")) \
        == {"ed25519": 4}


@pytest.mark.parametrize("signing,error,jobs", [
    (24, None, [2]),
    (12, "ErrInvalidHeader", [1]),  # over a third of the trusted set, not over two thirds of its own
    (8, "ErrNewValSetCantBeTrusted", []),
])
def test_only_the_trusting_tallys_shortfall_asks_for_a_bisection(
        traffic, host_route, monkeypatch, signing, error, jobs):
    """The light check's own shortfall refuses the header, with the
    trusting batch verified alone first; the trusting check's asks for a
    pivot and submits nothing (ref: light/verifier.go:70-95)."""
    import copy

    from tendermint_tpu.light import verifier as vf
    from tendermint_tpu.ops.engine import VerifyEngine
    from tendermint_tpu.types.block import CommitSig

    primary = traffic.new_client().primary
    trusted, new = primary.light_block(1), copy.deepcopy(primary.light_block(5))
    sigs = new.signed_header.commit.signatures
    sigs[signing:] = [CommitSig.new_absent() for _ in sigs[signing:]]
    calls, real = [], VerifyEngine.submit_together
    monkeypatch.setattr(VerifyEngine, "submit_together",
                        lambda self, batches: calls.append(len(batches)) or real(self, batches))

    def step():
        vf.verify_non_adjacent(
            "chain-churn", trusted.signed_header, trusted.validator_set, new.signed_header,
            new.validator_set, 1209600 * 10**9, new.signed_header.header.time, 10 * 10**9)

    if error is None:
        step()
    else:
        with pytest.raises(getattr(vf, error), match="insufficient voting power"):
            step()
    assert calls == jobs


def test_a_sequential_client_fetches_every_height_as_sequential(traffic, host_route):
    from tendermint_tpu.light.client import SEQUENTIAL

    client = traffic.new_client()
    client.mode = SEQUENTIAL
    fetched = _samples(light_metrics().fetches, "purpose")
    client.verify_light_block_at_height(5)
    assert _grown(fetched, _samples(light_metrics().fetches, "purpose")) \
        == {"target": 1, "sequential": 3, "witness": 1}


# ------------------------------------------------- the fill's programs


@jax.jit
def _jitted_tables(enc):
    """A table build that computes nothing but is, like the real one, a
    program of its row count."""
    return (jnp.zeros((enc.shape[0], V.PK_SPLITS, 16, 4, 32), jnp.int16),
            jnp.ones((enc.shape[0],), bool))


def _keys(lo: int, hi: int) -> list[bytes]:
    return [i.to_bytes(4, "big") * 8 for i in range(lo, hi)]


@pytest.fixture(scope="module")
def filled_cache():
    """tmdev installed, and a cache after one whole-batch fill at the
    128-row bucket: 101 keys looked up, 101 missed."""
    was = devobs.enabled()
    assert devobs.install() is True
    cache = V.PubkeyCache(capacity=512, build_fn=_jitted_tables, plane="churn_pk")
    slots, _, _ = cache.ensure_snapshot(_keys(0, 101))
    assert sorted(slots) == list(range(101))
    yield cache
    if not was:
        devobs.uninstall()


@pytest.mark.parametrize("misses", [1, 3, 13, 40])
def test_a_fill_of_any_miss_count_loads_no_program_after_the_buckets_first(filled_cache, misses):
    cache = filled_cache
    fresh = _keys(1000 * misses, 1000 * misses + misses)
    batch = _keys(0, 101 - misses) + fresh  # 101 rows, `misses` of them new
    compiles = devobs.status()["compiles"]
    launched = _samples(V._engine_metrics().kernel_launches, "kernel").get("pk_table_build", 0.0)
    slots, tables, oks = cache.ensure_snapshot(batch)
    assert devobs.status()["compiles"] == compiles
    assert _samples(V._engine_metrics().kernel_launches, "kernel")["pk_table_build"] == launched + 1
    # the old keys kept their slots, the new ones took fresh ones, and only those rows were written
    assert list(slots[: 101 - misses]) == list(range(101 - misses))
    assert len(set(slots)) == 101 and all(cache._lru[pk] == s for pk, s in zip(batch, slots))
    oks = np.asarray(oks)  # on the host: an eager jnp op here would be a program of its own
    assert oks[slots].all() and oks.sum() == len(cache._lru)
    again, _, _ = cache.ensure_snapshot(batch)
    assert list(again) == list(slots) and devobs.status()["compiles"] == compiles


def test_a_batch_that_holds_its_missing_keys_twice_fills_each_once(filled_cache):
    """Two checks of one commit in one launch: a key the trusted set
    holds beyond the light batch's prefix can be new to the cache in
    both halves. One build, one slot a key, every row its key's slot,
    and each row that waited for a table counted as missed."""
    cache = filled_cache
    fresh = _keys(70000, 70007)
    batch = _keys(0, 30) + fresh[:5] + _keys(0, 60) + fresh  # 102 rows: 5 new keys twice, 2 once
    m = V._engine_metrics()
    launched = _samples(m.kernel_launches, "kernel").get("pk_table_build", 0.0)
    rows = _samples(m.pk_cache_rows, "plane").get("churn_pk", 0.0)
    missed = _samples(m.pk_cache_missed_rows, "plane").get("churn_pk", 0.0)
    held = len(cache._lru)
    slots, _, oks = cache.ensure_snapshot(batch)
    assert _samples(m.kernel_launches, "kernel")["pk_table_build"] == launched + 1
    assert [int(s) for s in slots] == [cache._lru[pk] for pk in batch]
    assert len(cache._lru) == held + 7 and len({cache._lru[pk] for pk in fresh}) == 7
    assert np.asarray(oks)[slots].all() and not cache._pending and not cache._pinned
    assert _samples(m.pk_cache_rows, "plane")["churn_pk"] == rows + 102
    assert _samples(m.pk_cache_missed_rows, "plane")["churn_pk"] == missed + 12
    again, _, _ = cache.ensure_snapshot(batch)
    assert list(again) == list(slots)
    assert _samples(m.kernel_launches, "kernel")["pk_table_build"] == launched + 1


def test_a_fills_programs_are_named_for_the_batchs_bucket(filled_cache):
    """tmdev's compile events of a bucket's first fill: the build and
    the publish, each at the padded row count of the batch that missed
    (64 for a 51-row batch with 5 misses), nothing at the miss count's."""
    before = {(ev["fn"], ev["rows"]) for ev in devobs.status(tail=4096)["tail"]}
    filled_cache.ensure_snapshot(_keys(0, 46) + _keys(9000, 9005))
    new = {(ev["fn"], ev["rows"]) for ev in devobs.status(tail=4096)["tail"]} - before
    assert new == {("churn_pk_table_build", 64), ("churn_pk_table_publish", 64)}
