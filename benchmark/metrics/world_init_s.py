"""The first worker's first `setup.world_init` phase: rendezvous, the
state's snapshot to the host, the mesh, placing the state on the
devices."""

from lib import cell


def read(run):
    return cell.load_module("metrics", "_setup_phases").seconds(
        run, ["setup.world_init"])
