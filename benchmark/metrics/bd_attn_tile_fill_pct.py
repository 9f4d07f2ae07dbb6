"""Of the scores in the tiles the masked attention ran in the window's
fenced steps, the share the mask lets through (`attn_scores_needed` over
`attn_scores_run`): the rest is the masked part of the crossed tiles (80.0
at L 8192, b 4 over 1024 x 1024 tiles with the own-block term run as
tiles)."""

from lib import cell


def read(run):
    scores = cell.load_module("metrics", "_sdar_ops").window_scores(run)
    return None if scores is None else 100.0 * scores[0] / scores[1]
