"""Host milliseconds per serving round: the loop's own seconds (its
``loop.step`` spans, the ``loop_time`` counter) less the seconds the
server spent blocked on the device (its ``serve.wait`` spans,
``device_wait``), per round (``steps``). A program without the loop's
phase counter reports nothing."""


def read(run):
    st = run.stats
    if "loop_time" not in st or not st.get("steps"):
        return None
    return 1e3 * (st["loop_time"] - st["device_wait"]) / st["steps"]
