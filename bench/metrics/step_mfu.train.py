"""Model FLOP/s of the training rounds in the window over the chips' peak.

The model operations of one round come from ``bench/flops.py`` (forward
and backward, no recomputation); the runner counts the rounds."""


def read(run):
    flops = run.counters.get("model_flops_per_round")
    rounds = run.counters.get("rounds")
    dev = run.peaks["devices"].get(run.kind)
    if not flops or not rounds or dev is None:
        return None
    return 100.0 * flops * rounds / run.window_s / (run.chips
                                                    * dev["bf16_flops_per_s"])
