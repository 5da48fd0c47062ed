"""Mean seconds of the benchmark's fenced span around each job's sketch
build (``repro.engine.session(graph, "bf", ...)``) inside the window."""


def read(run):
    times = [t1 - t0 for name, t0, t1 in run.spans
             if name == "pgbench.sketch_build" and t0 >= run.window_t0]
    return sum(times) / len(times) if times else None
