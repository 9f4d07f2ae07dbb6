"""`edl train` started -> the first worker's `worker_devices` event: the
master is up, the worker process exists and has opened its chips."""


def read(run):
    opened = run.events_of("worker_devices", "worker")
    return opened[0]["ts"] - run.t_launch if opened else None
