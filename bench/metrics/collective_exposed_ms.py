"""Collective device time per round during which no other operation runs
on that chip, averaged over the chips. None where no collective ran."""


def read(run):
    rounds = run.counters.get("rounds")
    if run.trace is None or not rounds:
        return None
    coll, exposed = run.trace.collective_seconds()
    if coll <= 0:
        return None
    return 1e3 * exposed / rounds
