"""Caller layer (light/client.py `_verify_step`): trust steps that
succeeded (`light.verify_step` with `outcome="ok"`: two launches in
series where the step skips heights) per update (`light.update`; a
client's trust root, `mode="root"`, takes no step and is left out) that
ended in the slice. 1 where every target is reached directly; a
bisecting update takes one more for every pivot. None where no update
ended, or the program's span has no `outcome`."""


def updates_ended(ctx) -> int:
    return sum(sp["ends_in_slice"] for sp in ctx["spans"]
               if sp["name"] == "light.update" and sp["args"].get("mode") != "root")


def steps_per_update(ctx, outcome: str):
    """Steps that ended in the slice with this outcome, per update."""
    steps = [sp for sp in ctx["spans"] if sp["name"] == "light.verify_step"
             and sp["ends_in_slice"] and "outcome" in sp["args"]]
    updates = updates_ended(ctx)
    if not steps or not updates:
        return None
    return sum(sp["args"]["outcome"] == outcome for sp in steps) / updates


def read(ctx):
    return steps_per_update(ctx, "ok")
