"""Mean seconds of the benchmark's fenced span around each job's triangle
listing (``MiningSession.triangles()``) inside the window."""


def read(run):
    times = [t1 - t0 for name, t0, t1 in run.spans
             if name == "pgbench.triangles" and t0 >= run.window_t0]
    return sum(times) / len(times) if times else None
