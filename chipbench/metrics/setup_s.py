"""Process start to the start of the measured window: native build,
corpus, server, poll, staging, compile or replay of every kernel shape,
warm traffic. The reference's time is not in it."""


def compute(run):
    return run["setup_seconds"]
