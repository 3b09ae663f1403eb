"""Bytes the parties send each other per training round, as the program
counts them (``bytes_per_round``: blinded embeddings up, the global
embedding down, predictions up, loss signals down). A count, not a time."""


def read(run):
    return run.counters.get("uplink_bytes_per_round")
