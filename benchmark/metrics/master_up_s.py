"""`edl train` started -> the end of the master's `setup.master` phase:
the client's interpreter and imports, argument parsing, task creation
over the record file, the servicer, the port bound."""

from lib import cell


def read(run):
    up = cell.load_module("metrics", "_setup_phases").first(
        run, "setup.master", "master")
    return up[1] - run.t_launch if up else None
