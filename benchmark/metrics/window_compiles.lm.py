"""Programs compiled cold inside the window (expected: 0)."""


def read(run):
    a, b = run.t0, run.t1
    return float(len(run.events_of("compile", "worker", since=a, until=b)))
