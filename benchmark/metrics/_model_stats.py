"""What the routed layers counted in the steps whose loss was read inside
the window: the worker's `model_stats` events (one a fence, the step's own
counts summed over the routed layers), added up over the window. Steps
before the window (warm-up, where the load still moves fastest) are left
out. A program without the event gives None."""


def window_sums(run):
    events = run.events_of("model_stats", "worker", since=run.t0,
                           until=run.t1)
    if not events:
        return None
    names = ("moe_assignments", "moe_assignments_held",
             "moe_held_load_max", "moe_held_load_mean")
    return {n: sum(float(e.get(n, 0.0)) for e in events) for n in names}
