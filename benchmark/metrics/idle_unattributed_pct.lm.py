"""Share of the traced window in which the device was idle under no span
of the program (mean over the chips). Prints the idle seconds by span
and the longest gaps with their labels, and writes those labels into the
run's `idle_gaps` breakdown."""

from lib import cell


def read(run):
    layers = cell.load_module("metrics", "_host_span_layers")
    layers.relabel_and_print(run)
    return layers.idle_pct(run, layers.DEVICE)
