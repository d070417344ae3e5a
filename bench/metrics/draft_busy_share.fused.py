"""Share of the device's busy time spent in the drafting stage of the
fused round program: ops under its ``draft`` named scope, inside the
window, over the window's busy seconds (see ``harness/scopes.py``). A
program whose ops carry no such scope reports nothing."""
from harness import scopes


def read(run):
    red = run.trace
    if red is None or not red.busy_s:
        return None
    s = scopes.seconds("draft")
    return None if s is None else 100.0 * s / red.busy_s
