"""Caller layer (light/client.py `_verify_skipping_against_primary`):
what bisection costs beyond the steps that succeed: time inside refused
jumps (`light.verify_step` with `outcome="bisect"`) and inside pivot
fetches (`light.fetch` with `purpose="pivot"`: fetch, decode, basic
validation), per update (`light.update` other than a trust root's) that
ended in the slice. None where no update ended, or the program's
`light.fetch` says no `purpose` (then a pivot's fetch cannot be told
from a target's)."""

from benchmark.metrics.steps_per_update import updates_ended


def read(ctx):
    fetches = [sp for sp in ctx["spans"] if sp["name"] == "light.fetch"]
    updates = updates_ended(ctx)
    if not updates or not any("purpose" in sp["args"] for sp in fetches):
        return None
    ns = sum(sp["t1"] - sp["t0"] for sp in fetches if sp["args"].get("purpose") == "pivot")
    ns += sum(sp["t1"] - sp["t0"] for sp in ctx["spans"] if sp["name"] == "light.verify_step"
              and sp["args"].get("outcome") == "bisect")
    return ns / 1e6 / updates
