"""Share of the window the worker spent waiting for the master's task
RPC (`datapath` events, stage `task`)."""


def read(run):
    return run.stage_share_pct(("task",))
