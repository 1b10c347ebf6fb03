"""Caller layer, the whole window: `caller_offcpu_share`'s formula on
the one root `slowest_op_ms` reads: the share of the run's longest
request in which its thread was off the CPU outside
`verify.commit_collect`. Near 100 in a stall the thread sat out
(descheduled, or blocked on a lock or in the runtime: the logged
`runq_us`, where the kernel gives one, says which), near 0 in one it
computed through (a collection: `runtime.gc` is then among its
children). On a machine that counts CPU time in ticks of 10 ms, as the
chip's does, it reads true for a root of hundreds of milliseconds and
is a coin's toss for one of twenty."""

from benchmark.window_spans import slowest


def read(ctx):
    found = slowest(ctx)
    return found["offcpu_share"] if found else None
