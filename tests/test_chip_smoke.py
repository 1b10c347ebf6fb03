"""chip_smoke.py and the pieces it stands on: it refuses to run without
a TPU, its chain fixture is a function of the seed, and the compile
cache can be placed from outside."""

from __future__ import annotations

import ast
import json
import os
import subprocess
import sys
import time

import pytest

from tendermint_tpu.blocksync import fixture
from tendermint_tpu.metrics import engine_metrics

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra=None, timeout=60, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, *args], cwd=_ROOT, env=env, capture_output=True, text=True,
        timeout=timeout,
    )


def test_chip_smoke_refuses_cpu_at_once():
    """No TPU: non-zero exit within seconds, naming the platform, before
    any fixture work, and no result on stdout."""
    t0 = time.monotonic()
    out = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"}, timeout=60)
    assert out.returncode not in (0, None)
    assert time.monotonic() - t0 < 30
    assert "cpu" in out.stderr and "no TPU" in out.stderr
    assert out.stdout == ""


@pytest.mark.slow
def test_chip_smoke_dry_run():
    """Every phase's control flow at tiny sizes on XLA:CPU, the
    four-device phase included. Never a device reading."""
    out_dir = os.path.join(_ROOT, ".bench_runs", "chip_smoke_dry")
    out = _run(
        ["chip_smoke.py", "--dry-run", "--out", out_dir],
        {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
        timeout=900, drop=("TM_TPU_CRYPTO", "TM_TPU_FE_MUL"),
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    assert lines[0] == "DRY RUN platform=cpu"
    last = json.loads(lines[-1])
    assert last == {"ok": True, "dry_run": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    with open(os.path.join(out_dir, "chip_smoke_summary.json")) as f:
        summary = json.load(f)
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert [p["name"] for p in summary["phases"]] == [
        "native", "autotune", "fixture", "blocksync-1k", "refusal", "light-150",
        "localnet-4", "oracle", "sharded-4",
    ]
    assert all(p["ok"] for p in summary["phases"])


def _host_rows() -> float:
    return sum(v for _, labels, v in engine_metrics().path_rows.samples()
               if labels["path"] == "host")


def _kernel_launches() -> float:
    return sum(v for _, _, v in engine_metrics().kernel_launches.samples())


def test_chain_fixture_is_deterministic_in_the_seed():
    """Same seed, same chain, hash for hash; another seed, another
    chain. Four validators stay on the host route, and a joiner that
    block-syncs the chain arrives at the source's hashes."""
    before, launches = _host_rows(), _kernel_launches()
    a = fixture.build_chain(11, 4, 4)
    b = fixture.build_chain(11, 4, 4)
    c = fixture.build_chain(12, 4, 4)
    assert a.block_hashes == b.block_hashes and a.app_hashes == b.app_hashes
    assert [k.bytes() for k in a.keys] == [k.bytes() for k in b.keys]
    assert a.block_hashes != c.block_hashes
    assert a.height == 4 and a.state.last_block_height == 4
    deadline = time.monotonic() + 5  # the engine counts after waking the caller
    while _host_rows() - before < 3 * 3 * 4 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert _host_rows() - before >= 3 * 3 * 4  # 3 chains x 3 LastCommits x 4 signatures
    assert _kernel_launches() == launches  # and nothing went to the device

    res = fixture.sync(a, timeout=60)
    assert res.caught_up and res.fatal is None and not res.peer_errors
    assert res.blocks_synced == 3
    for h in (1, 2, 3):
        assert res.block_store.load_block(h).hash() == a.block_hashes[h - 1]
    assert res.state.app_hash == a.app_hashes[2]

    served = fixture.corrupted_copy(a, 2, 1)
    res = fixture.sync(a, serve_from=served, timeout=60, until_peer_error=True)
    assert not res.caught_up and res.fatal is None
    assert "wrong signature (#1)" in str(res.peer_errors[0].err)
    assert res.block_store.height() == 1  # height 2 refused


_CACHE_PROBE = (
    "import os, sys\n"
    "from tendermint_tpu.ops import enable_compile_cache\n"
    "import jax\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "print(repr((before, enable_compile_cache())))\n"
)


def test_compile_cache_helper_respects_the_environment():
    """JAX_COMPILATION_CACHE_DIR set: the helper sets no directory in
    code (jax read the variable at import). Unset: <checkout>/.jax_cache."""
    placed = "/tmp/placed-from-outside/jax_cache"
    out = _run(["-c", _CACHE_PROBE], {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": placed})
    assert out.returncode == 0, out.stderr
    assert ast.literal_eval(out.stdout) == (placed, placed)
    out = _run(["-c", _CACHE_PROBE], {"JAX_PLATFORMS": "cpu"}, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert out.returncode == 0, out.stderr
    assert ast.literal_eval(out.stdout) == (None, os.path.join(_ROOT, ".jax_cache"))
