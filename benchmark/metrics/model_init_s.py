"""The first worker's `setup.model_spec` + `setup.build_trainer` +
`setup.model_init` phases: the model module's imports, the trainer's
construction, the parameters initialised (the jitted `model_init`)."""

from lib import cell


def read(run):
    return cell.load_module("metrics", "_setup_phases").seconds(
        run, ["setup.model_spec", "setup.build_trainer",
              "setup.model_init"])
