"""Compile: programs compiled or loaded between the window's two ends.
Must read 0: 0 is the reading, not a missing one."""


def read(ctx):
    d = ctx["devobs"]
    return float(d["window_end"]["compiles"] - d["window_start"]["compiles"])
