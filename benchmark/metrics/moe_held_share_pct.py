"""Token-expert assignments that fell on the experts this chip holds, over
all the routers made, in the window's fenced steps (6.25 under even routing:
8 of 128 experts)."""

from lib import cell


def read(run):
    sums = cell.load_module("metrics", "_model_stats").window_sums(run)
    if not sums or not sums["moe_assignments"]:
        return None
    return 100.0 * sums["moe_assignments_held"] / sums["moe_assignments"]
