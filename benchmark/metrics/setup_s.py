"""run.py's start -> the start of the measured window: data generation,
job launch, chip open, step compile or cache load, warm-up steps."""


def read(run):
    return run.t0 - run.t_start
