"""Compile: programs XLA compiled or loaded from the persistent cache
before the window opened (tmdev counts both as a backend compile)."""


def read(ctx):
    return float(ctx["devobs"]["window_start"]["compiles"])
