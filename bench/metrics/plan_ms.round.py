"""Milliseconds per serving round of the cascade's host-side planning
before drafting: PLD retrieval, the per-slot Eq. 5 routing, the tree seeds
and their transfer (the server's ``serve.plan`` spans, the ``plan_time``
counter), per round (``steps``). A program without the counter reports
nothing."""


def read(run):
    st = run.stats
    if "plan_time" not in st or not st.get("steps"):
        return None
    return 1e3 * st["plan_time"] / st["steps"]
