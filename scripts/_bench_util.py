"""In-process stage deadlines for bench.py."""

from __future__ import annotations

import signal


class StageTimeout(Exception):
    pass


def _alarm_handler(signum, frame):
    raise StageTimeout()


class stage_deadline:
    """Best-effort in-process deadline: SIGALRM raises StageTimeout in
    the main thread. Cannot interrupt a C call that never returns to the
    interpreter, but never SIGKILLs the process — the chip is released
    by normal JAX client shutdown on exit."""

    def __init__(self, seconds: float):
        self.seconds = max(1.0, seconds)

    def __enter__(self):
        signal.signal(signal.SIGALRM, _alarm_handler)
        signal.setitimer(signal.ITIMER_REAL, self.seconds)

    def __exit__(self, *exc):
        signal.setitimer(signal.ITIMER_REAL, 0)
        return False
