"""Of the rows the grouped expert loops multiplied in the window's fenced
steps, the share that were assignments (`moe_block_rows_real` over
`moe_block_rows_run`): the rest is the padding of each held expert's last
block."""

from lib import cell


def read(run):
    rows = cell.load_module("metrics", "_lfm2_ops").window_block_rows(run)
    return None if rows is None else 100.0 * rows[0] / rows[1]
