"""Wall seconds of the window per whole mining job completed in it."""


def read(run):
    jobs = run.counters.get("jobs")
    return run.window_s / jobs if jobs else None
