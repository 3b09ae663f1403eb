"""Host time per round inside the classifier's compiled step call (the
program's ``train.step`` span): argument handling and dispatch of the
step, which the device waits for. None where the program keeps no
spans."""
from bench import program_spans


def read(run):
    got = program_spans.records(run)
    if got is None:
        return None
    return program_spans.mean_ms(program_spans.named(got[0], "train.step"))
