"""Of the rows the grouped expert loops multiplied in the window's fenced
steps of the Kanana 2 cut, the share that were assignments
(`moe_block_rows_real` over `moe_block_rows_run`): the rest is the padding
of each held expert's one block (`expert_block_rows` was chosen on this
fill). `moe_block_fill_pct`'s reading, whose list a test pins to the LFM2
cell."""

from lib import cell


def read(run):
    if cell.load_module("metrics", "_kanana_ops").sizes(run) is None:
        return None
    rows = cell.load_module("metrics", "_lfm2_ops").window_block_rows(run)
    return None if rows is None else 100.0 * rows[0] / rows[1]
