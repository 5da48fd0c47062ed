"""Share of the window in which no operation ran on the device (union of
the trace's device op intervals), in percent; mining cells."""


def read(run):
    t = run.trace
    if t is None or not t["busy_s"] or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
