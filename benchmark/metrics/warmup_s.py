"""The end of the first worker's `setup.first_dispatch` (the step's
compile or cache load and first enqueue) -> the window's start: the
warm-up steps, until the master has seen their records."""

from lib import cell


def read(run):
    warm = cell.load_module("metrics", "_setup_phases").warmup(run)
    return warm[1] - warm[0] if warm else None
