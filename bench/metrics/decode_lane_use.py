"""Share of the decode lane-rounds that served a token: the program's
counters ``serve.tokens`` (tokens harvested) over ``serve.lane_slots``
(protocol rounds run times lanes), in the window."""
from bench import program_spans


def read(run):
    got = program_spans.records(run)
    if got is None:
        return None
    c = got[1]
    if not c.get("serve.lane_slots"):
        return None
    return 100.0 * c.get("serve.tokens", 0) / c["serve.lane_slots"]
