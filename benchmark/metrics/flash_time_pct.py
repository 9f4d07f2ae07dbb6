"""The flash kernels' share of the device's busy time in the trace."""

from lib import cell


def read(run):
    helper = cell.load_module("metrics", "_pallas_attention")
    events = helper.kernel_events(run)
    if not events or not run.trace["busy_s"]:
        return None
    per_device = sum(e[-1] for e in events) / len(run.trace["devices"])
    return 100.0 * per_device / 1e9 / run.trace["busy_s"]
