"""Seconds from the start of the process to the end of set-up: imports,
weights, the program's state and the warm-up windows (compiles, or cache
reads)."""


def read(ctx):
    return ctx["setup_s"]
