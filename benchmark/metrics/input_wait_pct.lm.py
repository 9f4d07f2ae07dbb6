"""Share of the window the training loop spent blocked on its input:
starved on an empty prefetch queue, plus the consumer-side read, decode
and host-to-device stages (`datapath` events)."""


def read(run):
    return run.stage_share_pct(("starve", "read", "decode", "h2d"))
