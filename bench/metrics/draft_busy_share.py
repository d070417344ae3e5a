"""Share of the device's busy time spent in the drafting programs (the
cascade's or the tree's drafting scan run as a program of its own)."""
from harness import phases


def read(run):
    return phases.busy_share(run, "draft")
