"""The longest of the milliseconds between consecutive steps leaving the
device inside the window (`steps_done` events; in a traced run after the
trace was written): in a clean run within 1% of `step_ms_p90.lm`. Where
the program's step-done clock says of a step that its *stamp* was late
and not the step (a `step_stall` event with `cause: "late_stamp"`), the
interval that ends and the one that begins with that stamp are left
out."""

from lib import cell


def read(run):
    steps = cell.load_module("metrics", "_step_intervals")
    got = steps.intervals(run)
    if len(got) < steps.MIN_INTERVALS:
        return None
    late = {int(e["step"]) for e in run.events_of("step_stall", "worker")
            if e.get("cause") == "late_stamp"}
    kept = [ms for ms, step in got
            if step not in late and step - 1 not in late]
    return max(kept) if kept else None
