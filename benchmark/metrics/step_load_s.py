"""Seconds the first worker spent compiling its `*_step` program or
loading it from the compile cache, before the window."""


def read(run):
    return run.step_load_seconds(until=run.t0)
