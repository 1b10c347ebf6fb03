"""Engine (ops/engine.py `_autotune_probe`): the launch price the probe
drew the cutovers from, milliseconds: a warm 8-row per-signature launch
end to end. None where the probe did not run."""

from benchmark.routes import gauge


def read(ctx):
    seconds = gauge(ctx, "autotune_launch_seconds")
    return None if seconds is None else seconds * 1e3
