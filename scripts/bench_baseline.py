"""BASELINE configs benchmark (BASELINE.md / BASELINE.json):

  1. VerifyCommit, 4-validator commit (ed25519)          — latency floor
  2. VerifyCommitLightTrusting, 150 validators           — light client
  3. VerifyCommitLight, 1000 validators (blocksync-style)
  4. mixed ed25519+secp256k1 commit (serial fallback)
  5. 10k-signature mega-commit, sharded over the mesh

Each config measures the DEVICE path (TM_TPU_CRYPTO=on) and the host
path (TM_TPU_CRYPTO=off) on identical inputs, printing one JSON line
per config. Runs on whatever backend jax selects: the TPU, or the
virtual CPU mesh with
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

Usage: python scripts/bench_baseline.py [config ...] (default: all)
"""

from __future__ import annotations

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

from tendermint_tpu.crypto import ed25519 as E
from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
from tendermint_tpu.crypto.secp256k1 import Secp256k1PrivKey
from tendermint_tpu.proto.messages import BLOCK_ID_FLAG_COMMIT, SIGNED_MSG_TYPE_PRECOMMIT
from tendermint_tpu.types.block import BlockID, Commit, CommitSig, PartSetHeader
from tendermint_tpu.types.validation import (
    Fraction,
    verify_commit,
    verify_commit_light,
    verify_commit_light_trusting,
)
from tendermint_tpu.types.validator_set import Validator, ValidatorSet
from tendermint_tpu.types.vote import Vote
from tendermint_tpu.utils.tmtime import Time

CHAIN = "bench-chain"


def make_commit(n: int, mixed: bool = False, height: int = 5):
    keys = []
    for i in range(n):
        if mixed and i % 4 == 0:
            keys.append(Secp256k1PrivKey.generate(b"bench-%d" % i))
        else:
            keys.append(Ed25519PrivKey.generate((b"bench-%d" % i).ljust(32, b"\0")[:32]))
    vals = ValidatorSet.new([Validator.new(k.pub_key(), 10 if not (mixed and i % 4 == 0) else 100)
                             for i, k in enumerate(keys)])
    block_id = BlockID(hash=b"\x01" * 32, part_set_header=PartSetHeader(total=1, hash=b"\x02" * 32))
    ts = Time.now()
    by_addr = {v.address: i for i, v in enumerate(vals.validators)}
    sigs: list = [None] * n
    for k in keys:
        idx = by_addr[k.pub_key().address()]
        vote = Vote(type=SIGNED_MSG_TYPE_PRECOMMIT, height=height, round=0, block_id=block_id,
                    timestamp=ts, validator_address=k.pub_key().address(), validator_index=idx)
        sigs[idx] = CommitSig(block_id_flag=BLOCK_ID_FLAG_COMMIT,
                              validator_address=k.pub_key().address(), timestamp=ts,
                              signature=k.sign(vote.sign_bytes(CHAIN)))
    return vals, Commit(height=height, round=0, block_id=block_id, signatures=sigs)


def timed(fn, warmup: int = 1, iters: int = 5) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def with_backend(on: bool, fn):
    prev = os.environ.get("TM_TPU_CRYPTO")
    os.environ["TM_TPU_CRYPTO"] = "on" if on else "off"
    try:
        return fn()
    finally:
        if prev is None:
            os.environ.pop("TM_TPU_CRYPTO", None)
        else:
            os.environ["TM_TPU_CRYPTO"] = prev


def report(config: str, n_sigs: int, t_device: float, t_host: float) -> None:
    print(json.dumps({
        "config": config,
        "signatures": n_sigs,
        "device_ms": round(t_device * 1000, 3),
        "host_ms": round(t_host * 1000, 3),
        "speedup": round(t_host / t_device, 3) if t_device > 0 else None,
        "device_sigs_per_s": round(n_sigs / t_device, 1) if t_device > 0 else None,
    }), flush=True)


def config1():
    vals, commit = make_commit(4)
    run = lambda: verify_commit(CHAIN, vals, commit.block_id, commit.height, commit)
    report("1_verify_commit_4val", 4, with_backend(True, lambda: timed(run)),
           with_backend(False, lambda: timed(run)))


def config2():
    vals, commit = make_commit(150)
    run = lambda: verify_commit_light_trusting(CHAIN, vals, commit, Fraction(1, 3))
    report("2_light_trusting_150val", 150, with_backend(True, lambda: timed(run)),
           with_backend(False, lambda: timed(run)))


def config3():
    vals, commit = make_commit(1000)
    run = lambda: verify_commit_light(CHAIN, vals, commit.block_id, commit.height, commit)
    report("3_blocksync_light_1000val", 1000, with_backend(True, lambda: timed(run, iters=3)),
           with_backend(False, lambda: timed(run, iters=3)))


def config4():
    vals, commit = make_commit(64, mixed=True)
    run = lambda: verify_commit(CHAIN, vals, commit.block_id, commit.height, commit)
    report("4_mixed_keytype_64val", 64, with_backend(True, lambda: timed(run)),
           with_backend(False, lambda: timed(run)))


def config5():
    import jax

    from tendermint_tpu.crypto import ed25519_ref as ref
    from tendermint_tpu.parallel import sharded_verify as sv

    n = int(os.environ.get("BENCH_MEGA", "10000"))
    sk = ref.gen_privkey(b"\x42" * 32)
    pk = sk[32:]
    msgs = [b"mega-%d" % i for i in range(n)]
    sigs = [ref.sign(sk, m) for m in msgs]
    mesh = sv.make_mesh(len(jax.devices()))
    run = lambda: sv.verify_batch_sharded(mesh, [pk] * n, msgs, sigs)
    t_device = timed(run, warmup=1, iters=3)
    # host baseline on a sample (full 10k serial would dominate runtime)
    sample = 512
    t0 = time.perf_counter()
    for p, m, s in zip([pk] * sample, msgs[:sample], sigs[:sample]):
        E._single_verify(p, m, s)
    t_host = (time.perf_counter() - t0) * (n / sample)
    report(f"5_mega_commit_{n}sig_sharded_{len(jax.devices())}dev", n, t_device, t_host)


ALL = {"1": config1, "2": config2, "3": config3, "4": config4, "5": config5}

if __name__ == "__main__":
    picks = sys.argv[1:] or list(ALL)
    for p in picks:
        ALL[p]()
