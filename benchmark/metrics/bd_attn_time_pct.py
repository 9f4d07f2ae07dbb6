"""The flash kernels' share of the device's busy time in the trace, under
the block-diffusion mask: the forward and backward calls, known by the
instruction's own name (`bd_flash_fwd`, `bd_flash_bwd`; a rematerialised
forward counts: it is time the step spends)."""

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_sdar_ops")
    events = ops.kernel_events(run) if ops.sizes(run) else None
    if not events or not run.trace["busy_s"]:
        return None
    per_device = sum(e[-1] for e in events) / len(run.trace["devices"])
    return 100.0 * per_device / 1e9 / run.trace["busy_s"]
