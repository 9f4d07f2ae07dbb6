"""Share of the traced window in which the device was idle while the
worker's dispatching thread was feeding the step or fencing it:
`datapath.read/starve/decode/collate/h2d`, `worker.loss_fence`, or
`worker.step` alone (python between the stages); mean over the chips."""

from lib import cell


def read(run):
    layers = cell.load_module("metrics", "_host_span_layers")
    return layers.idle_pct(run, layers.WORKER_LOOP)
