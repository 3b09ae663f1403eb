"""Device time per round of the classifier's mask synthesis.

``MaskEngine.masks`` runs eagerly, outside the compiled train step: each
round dispatches a program per operation, named in the trace after the
operation (``jit__normal``, ``jit__threefry_fold_in``, ``jit_multiply``,
...). The reader sums the programs of that list, on the chip that spent
the most on them. It reads nothing unless a random-number program is
among them: masks made inside another program (the step, or a fused
kernel) cannot be told apart by name, and then the metric is left out
rather than read as nought.
"""

# the programs masks() dispatched on a TPU v5 lite: the pair streams'
# random bits and normals, the keys around them, the per-pair signs and
# scaling, the axis move and the scan that folds the pairs per party
MASK_PROGRAMS = ("jit__normal", "jit__threefry_", "jit__random_bits",
                 "jit__uniform", "jit_multiply", "jit__moveaxis",
                 "jit_scan", "jit_broadcast_in_dim",
                 "jit_convert_element_type", "jit_reshape")
RANDOM = ("jit__normal", "jit__threefry_", "jit__random_bits",
          "jit__uniform")


def read(run):
    rounds = run.counters.get("rounds")
    if run.trace is None or not rounds:
        return None
    progs = run.trace.program_seconds()
    if not any(k.startswith(RANDOM) for k in progs):
        return None
    masks = sum(v for k, v in progs.items() if k.startswith(MASK_PROGRAMS))
    return 1e3 * masks / rounds
