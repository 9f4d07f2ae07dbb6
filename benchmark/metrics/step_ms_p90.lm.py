"""90th percentile of the milliseconds between consecutive steps leaving
the device inside the window (about 170 intervals in 40 s, so 17 beyond
it). Prints the count and the longest interval with its step."""

from lib import cell


def read(run):
    return cell.load_module("metrics", "_step_intervals").percentile(
        run, 90, say=True)
