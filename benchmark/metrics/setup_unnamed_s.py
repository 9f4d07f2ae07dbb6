"""`run.t0 - run.t_launch` less the union of every `setup.*` phase of the
master and the first worker and of the warm-up: what of the set-up no
span of the program names. Prints the phases and the longest gaps."""

from lib import cell


def read(run):
    return cell.load_module("metrics", "_setup_phases").unnamed(
        run, say=True)
