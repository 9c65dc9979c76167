"""Set-up: process start to the window's start (loading, weights, compiles
or compile-cache loads, warm-up)."""


def read(run):
    return run.setup_s
