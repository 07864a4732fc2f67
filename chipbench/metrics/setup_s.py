"""Seconds from process start to the window's opening: imports, device
start, inputs, server, warm-up (and, on a cold cache, compilation)."""


def read(run):
    return run.setup_s
