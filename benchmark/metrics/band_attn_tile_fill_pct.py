"""Of the scores in the tiles the windowed layers' attention ran in the
window's fenced steps, the share the band lets through
(`band_scores_needed` over `band_scores_run`): the rest is the masked part
of the crossed tiles (50.0 at S 16384 under a window of 1024 over 1024 x
1024 tiles, where every run tile is crossed; 66.7 over 512 x 512)."""

from lib import cell


def read(run):
    scores = cell.load_module("metrics", "_mellum_ops").window_scores(run)
    return None if scores is None else 100.0 * scores[0] / scores[1]
