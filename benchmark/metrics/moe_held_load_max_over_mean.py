"""Largest over mean assignment count of the held experts (each summed
over the routed layers) in the window's fenced steps: 1 is even load; the
grouped product's blocks follow the largest."""

from lib import cell


def read(run):
    sums = cell.load_module("metrics", "_model_stats").window_sums(run)
    if not sums or not sums["moe_held_load_mean"]:
        return None
    return sums["moe_held_load_max"] / sums["moe_held_load_mean"]
