"""Model FLOP/s of the served work over the chips' peak: the prefill of
every prompt and every served token's decode (``bench/flops.py``), over
the window."""


def read(run):
    c = run.counters
    dev = run.peaks["devices"].get(run.kind)
    total = c.get("prefill_flops", 0.0) + c.get("decode_flops", 0.0)
    if dev is None or not total:
        return None
    return 100.0 * total / run.window_s / (run.chips
                                           * dev["bf16_flops_per_s"])
