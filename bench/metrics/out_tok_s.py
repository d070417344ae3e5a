"""Output tokens delivered inside the window, divided by the window."""


def read(run):
    return run.e2e["tokens"] / run.win.seconds
