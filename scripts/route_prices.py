"""What each device program costs at each padded batch size, on the chip in use.

The engine sends a device batch to the per-signature program or to the
MSM program by `MSM_CUTOVER_ROWS[device_kind]` (ops/engine.py). That
entry is this script's output: for every padded size from 64 rows to
MAX_COALESCE_ROWS it prints, warm and with every pubkey in the cache,

  launch ms — one batch from submission to verdicts on the calling
              thread (`verify_batch_cached_async` / `verify_batch_rlc_async`
              through `collect`): prep, staging, kernel, read-back;
  kernel ms — the program alone on arguments already on the device,
              queued back to back;

for `verify_kernel_cached_split` and `msm_verify_kernel`, the uncached
`verify_kernel` at 128 and 1024 rows (what a batch falls back to when
the cache overflows) and the host C loop (the route below the device
cutover). The entry for a device kind is the smallest size at which the
MSM's launch is the cheaper one, or a size past MAX_COALESCE_ROWS when
it never is.

    chiprun --timeout 3000 -- python3 scripts/route_prices.py

Exits 2 without a TPU: a price from XLA:CPU is never printed as one.
`--dry-run` walks the same code at 8 and 16 rows on whatever backend
there is. Rows are printed as they are measured and kept in
chiprun_out/route_prices.json.
"""

import argparse
import json
import os
import statistics
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

SIZES = (64, 128, 256, 512, 1024, 2048, 4096, 8192)
UNCACHED_SIZES = (128, 1024)
KEYS = 1024  # distinct validators; larger batches are several commits of one set
QUEUED = 4  # kernel calls queued back to back for one kernel-alone timing


def signed_rows(n):
    """n valid (pubkey, message, signature) rows over at most KEYS keys."""
    from tendermint_tpu.crypto import ed25519_ref as ref

    try:
        from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

        def signer(seed):
            return Ed25519PrivateKey.from_private_bytes(seed).sign
    except ImportError:
        def signer(seed):
            return lambda msg, sk=ref.gen_privkey(seed): ref.sign(sk, msg)

    seeds = [(i + 1).to_bytes(32, "little") for i in range(min(n, KEYS))]
    pubs = [ref.pubkey_from_seed(s) for s in seeds]
    sign = [signer(s) for s in seeds]
    msgs = [b"route-prices-%d" % i for i in range(n)]
    return ([pubs[i % len(pubs)] for i in range(n)], msgs,
            [sign[i % len(sign)](m) for i, m in enumerate(msgs)])


def timed_ms(fn, reps, per_call=1):
    """(median, least) milliseconds of `reps` calls of fn, each split over
    the per_call operations it makes."""
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e3 / per_call)
    return statistics.median(times), min(times)


def programs(rows, uncached):
    """name -> (launch thunk returning all-valid, kernel, its staged arguments)."""
    import jax.numpy as jnp

    from tendermint_tpu.ops import msm as M
    from tendermint_tpu.ops import verify as V

    pks, msgs, sigs = rows
    n = len(sigs)
    a, r, s, k, _ = V.prepare_batch(pks, msgs, sigs)
    slots, tables, oks = V.pubkey_cache().ensure_snapshot(pks)
    zk, z, zs = M._scalars_rlc(s, k, n, None)
    dev = lambda *arrays: tuple(jnp.asarray(x) for x in arrays)
    out = {
        "verify_kernel_cached_split": (
            lambda: V.collect(V.verify_batch_cached_async(pks, msgs, sigs)).all(),
            V.verify_kernel_cached_split, (tables, oks, *dev(slots, r, s, k))),
        "msm_verify_kernel": (
            lambda: M.collect_rlc(M.verify_batch_rlc_async(pks, msgs, sigs)),
            M.msm_verify_kernel, dev(a, r, zk, z, zs)),
    }
    if uncached:
        out["verify_kernel"] = (
            lambda: V.collect(V.verify_batch_async(pks, msgs, sigs)).all(),
            V.verify_kernel, dev(a, r, s, k))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="8 and 16 rows on any backend: control flow, not prices")
    ap.add_argument("--reps", type=int, default=9)
    args = ap.parse_args()

    import jax

    from tendermint_tpu.ops import engine as E

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.dry_run:
        print(f"no TPU: jax.devices()[0] is {device.platform}:{device.device_kind}",
              file=sys.stderr)
        return 2
    sizes, uncached_sizes = ((8, 16), (8,)) if args.dry_run else (SIZES, UNCACHED_SIZES)
    out_path = os.path.join(_ROOT, "chiprun_out", "route_prices.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    record = {"device_kind": device.device_kind, "jax": jax.__version__,
              "dry_run": args.dry_run, "rows": []}
    print(f"device_kind={device.device_kind!r} jax={jax.__version__} "
          f"cache={os.environ.get('JAX_COMPILATION_CACHE_DIR')!r}"
          + (" DRY RUN: not prices" if args.dry_run else ""), flush=True)
    print("| program | rows | launch ms (median, least) | kernel ms (median, least) | first call s |")
    print("|---|---|---|---|---|", flush=True)

    def report(program, n, launch, kernel, first_s):
        record["rows"].append({"program": program, "rows": n, "launch_ms": launch,
                               "kernel_ms": kernel, "first_call_s": first_s})
        cell = lambda pair: "-" if pair is None else f"{pair[0]:.3f}, {pair[1]:.3f}"
        print(f"| {program} | {n} | {cell(launch)} | {cell(kernel)} | {first_s:.1f} |", flush=True)
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1)

    all_rows = signed_rows(max(sizes))
    from tendermint_tpu.ops import verify as V

    t0 = time.perf_counter()
    V.pubkey_cache().ensure_snapshot(all_rows[0][:KEYS])  # one table build; hits from here on
    print(f"pubkey cache filled in {time.perf_counter() - t0:.1f} s", file=sys.stderr, flush=True)
    for n in sizes:
        rows = tuple(col[:n] for col in all_rows)
        host = lambda: all(E._HOST_VERIFY["ed25519"](*rows))
        t0 = time.perf_counter()
        if not host():
            raise RuntimeError(f"the host C loop refused a valid batch of {n} rows")
        report("host_c_loop", n, timed_ms(host, args.reps), None, time.perf_counter() - t0)
        for program, (launch, kernel, staged) in programs(rows, n in uncached_sizes).items():
            t0 = time.perf_counter()
            if not launch():  # loads or compiles the program; every row is valid
                raise RuntimeError(f"{program} at {n} rows refused a valid batch")
            first_s = time.perf_counter() - t0

            def back_to_back():
                outs = [kernel(*staged) for _ in range(QUEUED)]
                outs[-1].block_until_ready()

            back_to_back()
            report(program, n, timed_ms(launch, args.reps),
                   timed_ms(back_to_back, args.reps, per_call=QUEUED), first_s)
    return 0


if __name__ == "__main__":
    sys.exit(main())
