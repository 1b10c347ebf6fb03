"""Engine (ops/engine.py): groups the dispatch thread took off the queue
and sent on their way (`engine.dispatch`: one launch, or one call of
the host's C loop below the cutover) per update (`light.update`, a
client's trust root among them, as `verify_ms_per_update.light` counts
them) that ended in the slice. 2 where each of a trust step's two
checks is submitted and waited for alone, 1 where both enter the queue
together (`VerifyEngine.submit_together`) and leave it as one group; a
bisecting update pays that once for every step that succeeds, a refused
jump nothing. None where no update ended, or the program's trace holds
no `engine.dispatch`."""


def read(ctx):
    ended = {"engine.dispatch": 0, "light.update": 0}
    for sp in ctx["spans"]:
        if sp["name"] in ended:
            ended[sp["name"]] += sp["ends_in_slice"]
    if not ended["light.update"] or not ended["engine.dispatch"]:
        return None
    return ended["engine.dispatch"] / ended["light.update"]
