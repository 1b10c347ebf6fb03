"""Compile: seconds inside XLA's backend-compile event before the
window opened; with a warm cache, the time to load the programs."""


def read(ctx):
    return ctx["devobs"]["window_start"]["compile_seconds"] or None
