"""The latent attention's flash kernels' share of the device's busy time in
the trace: the forward and backward calls at keys of 192 against values of
128, known by the instruction's own name (`mla_flash_fwd`, `mla_flash_bwd`;
a rematerialised forward counts: it is time the step spends)."""

from lib import cell


def read(run):
    return cell.load_module("metrics", "_kanana_ops").time_share_pct(run)
