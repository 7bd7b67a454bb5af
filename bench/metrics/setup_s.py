"""Seconds from process start to the window's opening: imports, weights,
frame rendering, compile-cache loads (or compiles) and warm-up."""


def read(run):
    return run.setup_s
