"""Host prep and transfer (ops/verify.py, ops/msm.py, native/prep.c):
milliseconds a launch that the dispatching thread was inside
`ops.verify_dispatch` or `ops.msm_dispatch` and not on a core: the
spans' durations less their thread's CPU time (`cpu_us`, native code
that released the GIL counted as CPU), over the **whole window**, per
span (one a launch). Nothing under those spans waits by design (prep,
padding, the staging calls, an asynchronous launch), so what is read
here is the thread waiting for the GIL, for another lock, or for a
core. The spans' count, mean duration and mean CPU time go to the run's
log beside it. None on a program whose spans carry no `cpu_us`."""

from benchmark.window_spans import DISPATCHERS, clocked, offcpu_ns, window


def read(ctx):
    dispatches = clocked(window(ctx)["spans"], *DISPATCHERS)
    if not dispatches:
        return None
    n = len(dispatches)
    print(f"dispatch spans: n={n} "
          f"dur={sum(sp['t1'] - sp['t0'] for sp in dispatches) / 1e6 / n:.3f}ms "
          f"cpu={sum(sp['args']['cpu_us'] for sp in dispatches) / 1e3 / n:.3f}ms a launch, "
          "the whole window", flush=True)
    return offcpu_ns(dispatches) / 1e6 / n
