"""Seconds from process start to the window's start: start-up, weights,
server build, compilation or loading of every program, warm-up."""


def read(run):
    return run.setup_s
