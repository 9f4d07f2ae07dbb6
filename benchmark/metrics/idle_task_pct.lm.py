"""Share of the traced window in which the device was idle while the
worker's dispatching thread was under `datapath.task`, `worker.report_task`
or `worker.report_version` (mean over the chips): idle time the task
plane's round trips cost."""

from lib import cell


def read(run):
    layers = cell.load_module("metrics", "_host_span_layers")
    return layers.idle_pct(run, layers.TASK_PLANE)
