"""Programs JAX obtained per round for calls made inside
``EasterClassifier.masks``: the program's counter ``compiles.masks`` over
its ``masks`` spans. Each program counts once, whether the backend
compiled it or the persistent compilation cache held it (JAX times both
as one backend compile), so the reading counts the masks' misses of
JAX's in-memory cache and does not tell a compile from a load. 0 where
the masks obtain no program; None where the program keeps no spans."""
from bench import program_spans


def read(run):
    got = program_spans.records(run)
    if got is None:
        return None
    spans, counters = got
    rounds = len(program_spans.named(spans, "masks"))
    if not rounds:
        return None
    return counters.get("compiles.masks", 0) / rounds
