"""Host time per round inside ``EasterClassifier.masks`` (the program's
``masks`` span): the eager mask synthesis's tracing, compile-cache lookups
and dispatch. None where the program keeps no spans."""
from bench import program_spans


def read(run):
    got = program_spans.records(run)
    if got is None:
        return None
    return program_spans.mean_ms(program_spans.named(got[0], "masks"))
