"""Milliseconds per serving round spent calling jitted programs: argument
conversion, transfer and enqueue (the server's ``serve.dispatch`` spans,
the ``dispatch_time`` counter), per round (``steps``). A program without
the counter reports nothing."""


def read(run):
    st = run.stats
    if "dispatch_time" not in st or not st.get("steps"):
        return None
    return 1e3 * st["dispatch_time"] / st["steps"]
