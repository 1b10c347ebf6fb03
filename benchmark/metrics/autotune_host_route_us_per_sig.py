"""Engine (ops/engine.py `_autotune_probe`): the price of the host route
as the engine runs it, microseconds a signature: one 64-row batch
through the C loop, measured beside the probe and used for nothing yet.
None where the probe did not run."""

from benchmark.routes import gauge


def read(ctx):
    seconds = gauge(ctx, "autotune_host_route_sig_seconds")
    return None if seconds is None else seconds * 1e6
