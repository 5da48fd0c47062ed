"""Mean seconds of the benchmark's fenced span around each job's
Jarvis-Patrick query (``MiningSession.jarvis_patrick``) inside the window."""


def read(run):
    times = [t1 - t0 for name, t0, t1 in run.spans
             if name == "pgbench.jarvis_patrick" and t0 >= run.window_t0]
    return sum(times) / len(times) if times else None
