"""Share of the traced window in which the device was idle while the
worker's dispatching thread was inside the trainer: `trainer.dispatch`
(the enqueue; a compile inside the window would sit here) or
`trainer.world_check`; mean over the chips."""

from lib import cell


def read(run):
    layers = cell.load_module("metrics", "_host_span_layers")
    return layers.idle_pct(run, layers.TRAINER)
