"""Median of the milliseconds between consecutive steps leaving the
device inside the window (`steps_done` events; no fence needed)."""

from lib import cell


def read(run):
    return cell.load_module("metrics", "_step_intervals").percentile(run, 50)
