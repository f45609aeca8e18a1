"""Seconds from process start to the window: imports, the Trial Runner,
and loading the step programs the window will launch."""


def read(run):
    return run.setup_s
