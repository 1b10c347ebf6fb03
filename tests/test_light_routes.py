"""A light client's walk under each route the engine can give its
batches (host C loop, per-signature kernel, the same behind the pubkey
cache, two-phase MSM), held to the benchmark's plain reference: the
routes `light-150-skip` runs on the chip, at a size that compiles here.
With them the pubkey cache's row counters and the prices the autotune
probe publishes.
"""

import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp

import tendermint_tpu.crypto.ed25519 as ed
from benchmark import chain as chainlib
from benchmark.drivers.light import Traffic
from tendermint_tpu.light.verifier import ErrInvalidHeader
from tendermint_tpu.metrics import engine_metrics
from tendermint_tpu.ops import engine as E
from tendermint_tpu.ops import verify as V
from tendermint_tpu.proto import messages as pb
from tendermint_tpu.types.light_block import LightBlock

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_engine import _probe_prices, _unpinned_probe  # noqa: E402

# 9 equal validators: more than 1/3 is a 4-signature batch, more than
# 2/3 a 7-signature one, both padded to the 8-row programs that
# tests/test_engine.py compiles.
CONFIG = {"validators": 9, "voting_power": 10, "txs_per_block": 2, "chain_id": "chain-routes",
          "blocks": 9}
PARAMS = {"skips": [2, 3], "witnesses": 1, "trusting_period_s": 1209600, "check_sample": 4}
SEED = 2147483659  # past 31 bits, as the driver's seeds are

# route -> (device cutover, MSM cutover, TM_TPU_PK_CACHE, the engine's path label, kernels launched)
ROUTES = {
    "host": (100, 100, "on", "host", set()),
    "bitmap": (4, 100, "off", "bitmap", {"bitmap"}),
    "bitmap_cached": (4, 100, "on", "bitmap", {"bitmap_cached"}),
    "two_phase_msm": (4, 4, "on", "two_phase_msm", {"rlc"}),
}


@pytest.fixture(scope="module")
def traffic():
    t = Traffic(CONFIG, PARAMS, SEED)
    t.build()
    return t


def _samples(family, label, **where):
    """label value -> sample, over the samples whose other labels are `where`."""
    return {labels[label]: value for _, labels, value in family.samples()
            if all(labels[k] == v for k, v in where.items())}


def _grown(before, after):
    return {k: v - before.get(k, 0.0) for k, v in after.items() if v != before.get(k, 0.0)}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_a_walk_under_each_route_agrees_with_the_reference(traffic, monkeypatch, route):
    device, msm, pk_cache, path, kernels = ROUTES[route]
    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", device)
    monkeypatch.setattr(ed, "MSM_BATCH_CUTOVER", msm)
    monkeypatch.setenv("TM_TPU_PK_CACHE", pk_cache)
    chain, m = traffic.chain, engine_metrics()
    paths = _samples(m.launches, "path", plane="ed25519")
    launched = _samples(m.kernel_launches, "kernel")

    client = traffic.new_client()
    for height in traffic.schedule:
        lb = client.verify_light_block_at_height(height)
        assert lb.signed_header.commit.block_id.hash == chain.block_hashes[height - 1]
        stored = client.store.light_block(height)
        # benchmark/reference.commit_verdict on the 1/3 and the 2/3 check
        assert traffic._verdicts(stored.signed_header.commit) == (True, True)
    # every batch of the walk took the route under test, through its own kernel
    assert set(_grown(paths, _samples(m.launches, "path", plane="ed25519"))) == {path}
    assert set(_grown(launched, _samples(m.kernel_launches, "kernel"))) - {"pk_table_build"} \
        == kernels

    # one signature only the curve equation refuses, in the 1/3 batch and in
    # the 2/3 batch outside it: the client blames that row and stores nothing,
    # and the reference refuses the same commit
    height = traffic.schedule[0]
    trusting = chainlib.signing_prefix(chain, 1, 3)
    light = chainlib.signing_prefix(chain, 2, 3)
    rng = random.Random(SEED)
    for bad_index in (rng.randrange(trusting), rng.randrange(trusting, light)):
        forged = LightBlock.from_proto(pb.LightBlock.decode(traffic.encoded[height]))
        cs = forged.signed_header.commit.signatures[bad_index]
        cs.signature = chainlib.flip_s(cs.signature)
        assert not all(traffic._verdicts(forged.signed_header.commit))
        blocks = dict(traffic.encoded)
        blocks[height] = forged.to_proto().encode()
        liar = traffic.new_client(blocks)
        with pytest.raises(ErrInvalidHeader, match=rf"wrong signature \(#{bad_index}\)"):
            liar.verify_light_block_at_height(height)
        assert liar.store.light_block(height) is None


# ------------------------------------------------- the pubkey cache's counters


def _stub_tables(enc):
    """A table build that launches nothing: the counters count rows."""
    return (jnp.zeros((enc.shape[0], V.PK_SPLITS, 16, 4, 32), jnp.int16),
            jnp.ones((enc.shape[0],), bool))


KEYS = [bytes([i]) * 32 for i in range(8)]


@pytest.mark.parametrize("batches", [
    # (keys looked up, rows the batch must miss)
    pytest.param([(KEYS[:4], 4)], id="a_cold_batch_misses_every_row"),
    pytest.param([(KEYS[:4], 4), (KEYS[:4], 0), (KEYS[:3] + KEYS[:3], 0)],
                 id="the_same_batch_again_misses_none"),
    pytest.param([(KEYS[:4], 4), (KEYS[4:6], 2), (KEYS[:1], 1), (KEYS[4:6], 0)],
                 id="an_evicted_key_misses_again"),
    pytest.param([(KEYS[:5], 5), (KEYS[:5], 5)], id="a_batch_the_cache_cannot_hold_misses_whole"),
])
def test_the_pubkey_cache_counts_rows_looked_up_and_rows_missed(batches):
    cache = V.PubkeyCache(capacity=4, build_fn=_stub_tables, plane="counted_pk")
    m = engine_metrics()
    for keys, want_missed in batches:
        rows = _samples(m.pk_cache_rows, "plane").get("counted_pk", 0.0)
        missed = _samples(m.pk_cache_missed_rows, "plane").get("counted_pk", 0.0)
        slots, _, _ = cache.ensure_snapshot(keys)
        assert (slots is None) == (len(set(keys)) > cache.capacity)
        assert _samples(m.pk_cache_rows, "plane")["counted_pk"] - rows == len(keys)
        assert _samples(m.pk_cache_missed_rows, "plane")["counted_pk"] - missed == want_missed


# ------------------------------------------------- the autotune probe's prices

PRICES = ("autotune_host_sig_seconds", "autotune_launch_seconds",
          "autotune_host_route_sig_seconds")


def _prices():
    m = engine_metrics()
    return {name: [value for _, _, value in getattr(m, name).samples()] for name in PRICES}


@pytest.fixture
def unpriced():
    """No price published before the test, none left behind it."""
    m = engine_metrics()
    for name in PRICES:
        getattr(m, name).remove()
    yield
    for name in PRICES:
        getattr(m, name).remove()


def test_the_prices_stay_unset_without_an_accelerator(unpriced, monkeypatch):
    monkeypatch.setitem(E._AUTOTUNE, "done", False)
    before = (ed.DEVICE_BATCH_CUTOVER, ed.MSM_BATCH_CUTOVER)
    E.maybe_autotune()
    assert _prices() == {name: [] for name in PRICES}
    assert (ed.DEVICE_BATCH_CUTOVER, ed.MSM_BATCH_CUTOVER) == before


def test_a_probe_publishes_the_prices_it_drew_the_device_cutover_from(unpriced, monkeypatch):
    _unpinned_probe(monkeypatch)
    # a host verification of 1 unit, a launch of 20: the device cutover
    # drawn from them is 32; the MSM cutover is left to the table of
    # measured crossovers (no entry for this device kind: the default
    # stays); the host route's 64-row batch takes 3.2
    _probe_prices(monkeypatch, launch=20.0)
    E.maybe_autotune()
    assert E._device_kind() not in E.MSM_CUTOVER_ROWS
    assert (ed.DEVICE_BATCH_CUTOVER, ed.MSM_BATCH_CUTOVER) == (32, 256)
    prices = _prices()
    assert prices["autotune_host_sig_seconds"] == [pytest.approx(1.0)]
    assert prices["autotune_launch_seconds"] == [pytest.approx(20.0)]
    assert prices["autotune_host_route_sig_seconds"] == [pytest.approx(0.05)]


def test_a_probe_that_fails_publishes_no_price(unpriced, monkeypatch):
    def unavailable(*a):
        raise RuntimeError("UNAVAILABLE: TPU backend setup/compile error")

    _unpinned_probe(monkeypatch)
    monkeypatch.setattr(V, "verify_batch", unavailable)
    E.maybe_autotune()
    assert _prices() == {name: [] for name in PRICES}
