"""Subsystem micro-benchmarks (ref: the reference's *_bench_test.go
harnesses — mempool/cache, light client, sign-bytes, block execution).

Prints one JSON line per benchmark. Host-side only (no TPU needed):

  JAX_PLATFORMS=cpu python scripts/bench_subsystems.py
"""

from __future__ import annotations

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "tests"))


def bench(name, fn, n, unit="ops/s", warmup=False):
    if warmup:
        # first call absorbs one-time costs (imports, the crypto
        # device-presence probe) so the rate reflects steady state
        fn()
    t0 = time.perf_counter()
    fn()
    dt = time.perf_counter() - t0
    print(json.dumps({"bench": name, "n": n, "secs": round(dt, 4),
                      "rate": round(n / dt, 1), "unit": unit}), flush=True)


def bench_mempool_checktx(n=2000):
    """ref: internal/mempool/mempool_bench_test.go."""
    from tendermint_tpu.abci import LocalClient
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.mempool.mempool import TxMempool

    mp = TxMempool(LocalClient(KVStoreApplication()), size=n + 10)
    txs = [b"k%d=v%d" % (i, i) for i in range(n)]

    def run():
        for tx in txs:
            mp.check_tx(tx)

    bench("mempool_checktx", run, n, "txs/s")


def bench_tx_cache(n=50000):
    """ref: internal/mempool/cache_bench_test.go."""
    from tendermint_tpu.mempool.mempool import LRUTxCache

    cache = LRUTxCache(n)
    txs = [b"cache-tx-%d" % i for i in range(n)]

    def run():
        for tx in txs:
            cache.push(tx)

    bench("mempool_cache_push", run, n, "txs/s")


def bench_sign_bytes(n=5000):
    """ref: types/vote_test.go:573 BenchmarkVoteSignBytes."""
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.vote import Vote
    from tendermint_tpu.utils.tmtime import Time

    vote = Vote(type=1, height=1001, round=2,
                block_id=BlockID(hash=b"\x88" * 32,
                                 part_set_header=PartSetHeader(total=3, hash=b"\x77" * 32)),
                timestamp=Time.now(), validator_address=b"\x11" * 20, validator_index=23)

    def run():
        for _ in range(n):
            vote.sign_bytes("bench-chain")

    bench("vote_sign_bytes", run, n)


def bench_light_verify(n=50, vals=20):
    """ref: light/client_benchmark_test.go (adjacent verification)."""
    from helpers import make_keys, make_validator_set, sign_commit
    from tendermint_tpu.light.verifier import verify_adjacent
    from tendermint_tpu.types.block import BlockID, Header, PartSetHeader
    from tendermint_tpu.types.light_block import SignedHeader
    from tendermint_tpu.utils.tmtime import Time

    keys = make_keys(vals)
    vset = make_validator_set(keys)

    def make_sh(height, t_ns):
        hdr = Header(chain_id="bench-chain", height=height, time=Time.from_unix_ns(t_ns),
                     validators_hash=vset.hash(), next_validators_hash=vset.hash(),
                     last_block_id=BlockID(hash=b"\x01" * 32,
                                           part_set_header=PartSetHeader(total=1, hash=b"\x02" * 32)),
                     proposer_address=vset.validators[0].address)
        bid = BlockID(hash=hdr.hash(), part_set_header=PartSetHeader(total=1, hash=b"\x03" * 32))
        commit = sign_commit("bench-chain", vset, keys, height, 0, bid, Time.from_unix_ns(t_ns))
        return SignedHeader(header=hdr, commit=commit)

    base_ns = Time.now().unix_ns()
    trusted = make_sh(10, base_ns)
    untrusted = make_sh(11, base_ns + 1_000_000_000)
    now = Time.from_unix_ns(base_ns + 2_000_000_000)

    def run():
        for _ in range(n):
            verify_adjacent("bench-chain", trusted, untrusted, vset,
                            3600 * 10**9, now, 10**9)

    # warmup absorbs the one-time crypto device-presence probe (~2.4s
    # jax import) that otherwise dominates and misreports the rate
    bench(f"light_verify_adjacent_{vals}val", run, n, "headers/s", warmup=True)


def bench_block_production(n=30):
    """End-to-end single-validator block production (consensus + ABCI +
    stores + WAL discipline) — the e2e cadence analog of
    test/e2e/runner/benchmark.go, in-process."""
    from helpers import make_genesis_doc, make_keys
    from test_consensus import fast_params, make_node, wait_for_height

    keys = make_keys(1)
    gen_doc = make_genesis_doc(keys, "bench-chain")
    gen_doc.consensus_params = fast_params()
    node = make_node(keys, 0, gen_doc)
    node.start()
    try:
        t0 = time.perf_counter()
        assert wait_for_height([node], n, timeout=120)
        dt = time.perf_counter() - t0
        print(json.dumps({"bench": "block_production_1val", "n": n,
                          "secs": round(dt, 3), "rate": round(n / dt, 2),
                          "unit": "blocks/s"}), flush=True)
    finally:
        node.stop()


ALL = {
    "mempool": bench_mempool_checktx,
    "cache": bench_tx_cache,
    "signbytes": bench_sign_bytes,
    "light": bench_light_verify,
    "exec": bench_block_production,
}

if __name__ == "__main__":
    picks = sys.argv[1:] or list(ALL)
    for p in picks:
        try:
            ALL[p]()
        except Exception as e:
            print(json.dumps({"bench": p, "error": repr(e)}), flush=True)
            raise SystemExit(1)
