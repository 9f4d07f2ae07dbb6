"""The first worker's `setup.open_devices` phase: the jax import and the
chips opened."""

from lib import cell


def read(run):
    return cell.load_module("metrics", "_setup_phases").seconds(
        run, ["setup.open_devices"])
