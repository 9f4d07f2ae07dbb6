"""Time in all-reduce operations during which no other operation runs on
that device, as a share of the traced window (mean over the chips). An
asynchronous all-reduce shows as a start and a done operation; the wait
is in the done."""

from lib import trace


def _is_all_reduce(name):
    p = trace.parse(name)
    return p is not None and p[2].startswith("all-reduce")


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0 or not trace.matching(t, _is_all_reduce):
        return None
    return 100.0 * trace.exposed_seconds(t, _is_all_reduce) / t["window_s"]
