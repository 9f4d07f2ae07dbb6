"""The master's `setup.spawn` of the first worker -> the end of that
worker's `setup.imports`: the fork, the interpreter's start, the
package's imports, the observability plane."""

from lib import cell


def read(run):
    phases = cell.load_module("metrics", "_setup_phases")
    imports = phases.first(run, "setup.imports", "worker")
    spawned = [
        start for name, start, _, role in phases.phases(run)
        if name == "setup.spawn" and role == "master"
        and imports and start <= imports[0]]
    return imports[1] - spawned[-1] if spawned else None
