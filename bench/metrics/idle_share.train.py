"""Device idle share of a training window: 1 - busy union / window."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * run.trace.idle_share
