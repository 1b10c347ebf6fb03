"""The engine's sharded route (ops/engine.py `splits`, parallel/sharded_verify.py
`dispatch` / `collect`) on four of conftest's eight virtual CPU devices:
the bitmap of a batch split across the chips against the oracle, at row
counts four does not divide, alone and through the engine for a
coalesced group, for both batch-capable planes; a bad row in each
chip's share blamed at its own index; the routing rule as the pure
function of chips and rows it is; the mesh's pubkey cache, one size and
one fill bucket. The cache is cut to 128 slots (conftest's
`small_mesh_cache`)."""

import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(__file__))

import tendermint_tpu.crypto.ed25519 as ed
from tendermint_tpu.crypto import ed25519_ref as ref
from tendermint_tpu.crypto import sr25519 as sr
from tendermint_tpu.ops import engine as E
from tendermint_tpu.parallel import sharded_verify as S

from test_batch_verify import make_jobs

CHIPS = 4


@pytest.fixture
def mesh(small_mesh_cache):
    return S.make_mesh(CHIPS)


def make_sr_jobs(n, tamper_idx=()):
    """n sr25519 rows, a key each, with the rows in tamper_idx broken."""
    pks, msgs, sigs = [], [], []
    for i in range(n):
        priv = sr.Sr25519PrivKey.generate(b"sharded-sr-%d" % i)
        msg = b"sharded-sr-vote-%d" % i
        sig = priv.sign(msg)
        if i in tamper_idx:
            sig = sig[:2] + bytes([sig[2] ^ 1]) + sig[3:]
        pks.append(priv.pub_key().bytes())
        msgs.append(msg)
        sigs.append(sig)
    return pks, msgs, sigs


ORACLES = {
    "ed25519": (make_jobs, lambda p, m, s: ref.verify(p, m, s, zip215=True)),
    "sr25519": (make_sr_jobs, sr.verify),
}


@pytest.mark.parametrize("key_type, n, bad", [
    ("ed25519", 5, {1}), ("ed25519", 37, {0, 36}), ("ed25519", 67, {17, 18, 50}),
    ("sr25519", 5, {1}), ("sr25519", 37, {0, 20, 36}),
])
def test_the_sharded_bitmap_is_the_oracles(mesh, key_type, n, bad):
    jobs, oracle = ORACLES[key_type]
    pks, msgs, sigs = jobs(n, tamper_idx=bad)
    handle = S.dispatch(mesh, pks, msgs, sigs, key_type)
    got = [bool(b) for b in S.collect(handle)]
    assert got == [oracle(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    assert got == [i not in bad for i in range(n)]
    assert not bool(handle[1])  # the psum AND-reduce saw the bad rows


def test_the_padding_schedule_per_chip():
    assert [S.chip_rows(n, CHIPS) for n in (5, 37, 67, 6667, 10000)] == [8, 16, 32, 1792, 2560]
    assert S.chip_rows(10000, 8) == 1280


@pytest.mark.parametrize("chip", range(CHIPS))
def test_a_bad_row_in_each_chips_share_is_blamed_at_its_own_index(mesh, chip):
    n = 37  # 16 rows a chip, the last chip's share short
    per = S.chip_rows(n, CHIPS)
    bad = min(chip * per + per // 2 + 1, n - 1)
    pks, msgs, sigs = make_jobs(n, tamper_idx={bad})
    bitmap, all_valid = S.verify_batch_sharded(mesh, pks, msgs, sigs)
    assert not all_valid
    assert [i for i, ok in enumerate(bitmap) if not ok] == [bad]


@pytest.mark.parametrize("chips, rows, split", [
    (1, 10000, False),
    (CHIPS, CHIPS * E.SHARD_MIN_ROWS - 1, False),
    (CHIPS, CHIPS * E.SHARD_MIN_ROWS, True),
    (CHIPS, 1667, False),  # the largest group of a one-chip cell
    (CHIPS, 6667, True),
    (CHIPS, 10000, True),
    (8, 4095, False),
])
def test_the_routing_rule(chips, rows, split):
    assert E.splits(chips, rows) is split


@pytest.mark.parametrize("key_type", sorted(ORACLES))
def test_a_coalesced_group_through_the_engine(monkeypatch, mesh, key_type):
    """Three callers' jobs in one group split over the chips: each
    caller gets its own rows' verdicts, and the launch is counted under
    path="sharded"."""
    from tendermint_tpu.metrics import engine_metrics

    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", 4)
    monkeypatch.setattr(E, "SHARD_MIN_ROWS", 4)
    make = ORACLES[key_type][0]
    jobs = [make(11, tamper_idx={3}), make(14), make(12, tamper_idx={0, 11})]

    def sharded():
        return sum(v for _, labels, v in engine_metrics().launches.samples()
                   if labels["path"] == "sharded")

    before = sharded()
    eng = E.VerifyEngine(mesh=mesh)
    handles = eng.submit_together([(key_type, *job, None) for job in jobs])
    got = [h.result(timeout=300) for h in handles]
    assert got == [[i != 3 for i in range(11)], [True] * 14,
                   [i not in (0, 11) for i in range(12)]]
    deadline = time.monotonic() + 10
    while sharded() == before and time.monotonic() < deadline:
        time.sleep(0.01)
    assert sharded() == before + 1


def test_an_engine_with_no_mesh_keeps_its_one_chip_routes(monkeypatch):
    """On a process with one chip (here: CPU devices, which the engine
    never splits over) the same group takes the per-signature route."""
    monkeypatch.setattr(ed, "DEVICE_BATCH_CUTOVER", 4)
    monkeypatch.setattr(ed, "MSM_BATCH_CUTOVER", 1 << 30)
    monkeypatch.setattr(E, "SHARD_MIN_ROWS", 4)
    pks, msgs, sigs = make_jobs(20, tamper_idx={7})
    thunk, path = E.VerifyEngine()._dispatch_group([E._Job("ed25519", pks, msgs, sigs)])
    assert path == "bitmap"
    assert thunk() == [i != 7 for i in range(20)]


def test_the_mesh_cache_holds_the_whole_set_on_every_chip(mesh):
    """The route's pubkey tables stay on the mesh, replicated, so a
    launch stages only its rows; one cache a plane, of one size."""
    pks, msgs, sigs = make_jobs(37)
    S.verify_batch_sharded(mesh, pks, msgs, sigs)
    cache = S.mesh_cache(mesh, "ed25519")
    assert cache.capacity == S.CACHE_SLOTS == 128
    assert cache.tables.sharding.is_fully_replicated
    assert cache.tables.sharding.device_set == set(mesh.devices.flat)
    assert S.mesh_cache(mesh, "ed25519") is cache
    assert S.mesh_cache(mesh, "sr25519") is not cache


def test_the_mesh_cache_holds_any_valid_set():
    """On the chip: every key of the largest set a commit can carry, so
    the cache never grows, and no program that reads it loads twice."""
    from tendermint_tpu.types.validator_set import MAX_VOTES_COUNT

    assert MAX_VOTES_COUNT <= S.CACHE_SLOTS < 2 * MAX_VOTES_COUNT


@pytest.mark.parametrize("proof, commit", [(3, 4), (11, 16), (67, 100), (86, 128)])
def test_a_light_proof_then_its_full_commit_fill_one_cache_at_one_bucket(mesh, proof, commit):
    """A block's two batches, the 2/3 proof first: each fills the keys
    it brings at CACHE_SLOTS rows (one build and one publish program
    whatever the misses), the proof's keys keep their slots, and the
    cache is the one the proof filled."""
    from tendermint_tpu.metrics import engine_metrics

    def fill_rows():
        return sum(v for _, labels, v in engine_metrics().pk_cache_fill_rows.samples()
                   if labels["plane"] == "ed25519_sharded_pk")

    pks, msgs, sigs = make_jobs(commit, tamper_idx={commit - 1})
    before = fill_rows()
    first = S.collect(S.dispatch(mesh, pks[:proof], msgs[:proof], sigs[:proof]))
    cache = S.mesh_cache(mesh, "ed25519")
    slots = cache.ensure(pks[:proof])
    second = S.collect(S.dispatch(mesh, pks, msgs, sigs))
    assert first.all() and [bool(b) for b in second] == [i != commit - 1 for i in range(commit)]
    assert S.mesh_cache(mesh, "ed25519") is cache
    assert cache.ensure(pks[:proof]).tolist() == slots.tolist()
    assert fill_rows() - before == 2 * S.CACHE_SLOTS
