"""Share of its roofline the decode program reaches over the window.

Least time = max(model operations / peak FLOP/s, bytes / HBM peak), with
the operations of every served token and the bytes the algorithm needs
(``bench/flops.py``): once per protocol round every party's backbone
layers and projection and the active party's decision net and head; per
served token one embedding row in every party's table and the live keys
and values. Neither a whole embedding table nor a re-stacking copy
counts.
Divided by the decode program's device time in the trace."""


def read(run):
    c = run.counters
    dev = run.peaks["devices"].get(run.kind)
    if run.trace is None or dev is None or not c.get("decode_rounds"):
        return None
    t = run.trace.program_seconds().get(c["decode_program"])
    if not t:
        return None
    least = max(c["decode_flops"] / dev["bf16_flops_per_s"],
                c["decode_bytes"] / dev["hbm_bytes_per_s"])
    return 100.0 * least / t
