"""Tokens a second of the whole job (not per chip): records trained between
the first and the last fenced step line of the window, times the tokens a
record trains."""


def read(run):
    rate = run.record_rate()
    if rate is None:
        return None
    return rate * int(run.config["record_tokens"])
