"""Seconds from process start to the window's opening: weights, cluster,
compiles (or compile-cache loads), warm-up and the traffic's lead-in."""


def read(run):
    return run.setup_s
