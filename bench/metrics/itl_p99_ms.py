"""p99 of every gap between two delivered batches of one request's tokens,
both inside the window, over all requests."""
from harness import measure


def read(run):
    return measure.ms(run.e2e["itl"], 99)
