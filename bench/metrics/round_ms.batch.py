"""Window milliseconds per serving round (server ``steps`` counter)."""


def read(run):
    if not run.stats.get("steps"):
        return None
    return run.win.seconds * 1e3 / run.stats["steps"]
