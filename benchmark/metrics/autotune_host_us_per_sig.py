"""Engine (ops/engine.py `_autotune_probe`): the host price the probe
drew the cutovers from, microseconds a signature: sixteen single
verifications one at a time. None where the probe did not run."""

from benchmark.routes import gauge


def read(ctx):
    seconds = gauge(ctx, "autotune_host_sig_seconds")
    return None if seconds is None else seconds * 1e6
