"""Caller layer (light/client.py `_verify_step`): jumps the trusting
check refused for want of voting power (`light.verify_step` with
`outcome="bisect"`: a whole commit walk that launches nothing, answered
by a pivot fetch) per update (`light.update` other than a trust root's)
that ended in the slice. 0 on a chain whose set never moves. None where
no update ended, or the program's span has no `outcome`."""

from benchmark.metrics.steps_per_update import steps_per_update


def read(ctx):
    return steps_per_update(ctx, "bisect")
