"""Closed loop of EASTER language-model training chunks.

``api.build_trainer(...).run`` advances ``chunk`` optimizer steps in one
compiled dispatch: per step every party's backbone and embedding, mask
synthesis, blinding and aggregation, every party's decision net, LM head
and loss, backward and update. Set-up builds the trainer and its state and
runs its first chunk (which compiles it); the window runs further chunks
back to back on the same state. After the window the plain reference
(``bench/reference/lm.py``) follows that first chunk's steps from the same
weights and tokens.

Configuration keys: see ``bench/lm_system.py``, plus ``train``. Traffic
keys: ``batch``, ``seq``, ``chunk``, ``distinct_chunks``,
``zipf_exponent``, ``queue_depth``.
"""
from __future__ import annotations

import collections
import time


def token_batches(ctx) -> list:
    """Host batches, one per step of every distinct chunk, from the seed."""
    from bench import gen
    tr = ctx.traffic
    n = tr["chunk"] * tr["distinct_chunks"]
    toks = gen.zipf_tokens(ctx.rng(3), (n, tr["batch"], tr["seq"] + 1),
                           ctx.config["vocab_size"], tr["zipf_exponent"])
    return [{"tokens": t[:, :-1], "labels": t[:, 1:]} for t in toks]


def readings(prog: dict, ref: dict) -> dict:
    from bench import compare
    return {
        "loss_gap": compare.loss_gap(prog["loss"], ref["loss"]),
        "change_gap": compare.norm_gap(
            compare.flat(prog["change"]), compare.flat(ref["change"]),
            compare.moved_leaves(compare.flat(ref["grad1"]))),
    }


def reference(ctx, shapes, host, *, lowp=None, rows=None, lr=None) -> dict:
    """The reference over the first chunk's steps; ``rows`` keeps only
    that many rows of each batch and ``lr`` replaces the learning rate
    (planted faults)."""
    from bench import lm_system
    from bench.reference import lm as ref
    t = dict(ctx.config["train"])
    if lr is not None:
        t["lr"] = lr
    steps = [{k: v[:rows] for k, v in b.items()}
             for b in host[:ctx.traffic["chunk"]]]
    out = ref.sgd_follow(lm_system.initial_parties(ctx, shapes), steps,
                         ctx.config, lr=t["lr"], grad_clip=t["grad_clip"],
                         store_dtype=t["param_dtype"], lowp=lowp)
    out["change"] = ref.change_norms(out.pop("params"),
                                     lm_system.initial_parties(ctx, shapes))
    return out


def control(ctx, mode: str) -> dict:
    """``control``: the reference with every matrix product's operands in
    float8 (the next precision below the configuration's bfloat16);
    ``half_batch``: the reference fed half of every batch; ``unchanged``:
    a step that hands back its parameters as it got them."""
    from bench import lm_system
    _, shapes = lm_system.build(ctx)
    host = token_batches(ctx)
    ref = reference(ctx, shapes, host)
    if mode == "control":
        prog = reference(ctx, shapes, host, lowp="float8_e4m3fn")
    elif mode == "half_batch":
        prog = reference(ctx, shapes, host, rows=ctx.traffic["batch"] // 2)
    elif mode == "unchanged":
        # every step's loss is taken at the initial parameters
        prog = {"loss": reference(ctx, shapes, host, lr=0.0)["loss"],
                "change": [[0.0] * len(p) for p in ref["change"]]}
    else:
        raise ValueError(f"no control mode {mode!r}")
    return readings(prog, ref)


def run(ctx):
    import gc
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import api
    from bench import flops, lm_system
    from bench.harness import Check, Outcome
    from bench.reference import lm as ref

    tr, t = ctx.traffic, ctx.config["train"]
    system, shapes = lm_system.build(ctx)
    trainer = api.build_trainer(system, api.TrainConfig(
        optimizer=t["optimizer"], lr=t["lr"], grad_clip=t["grad_clip"],
        chunk=tr["chunk"]))
    state = trainer.init({"parties": lm_system.initial_parties(ctx, shapes)})
    ctx.log("federation built, weights made")
    host = token_batches(ctx)
    dev = [{k: jnp.asarray(v) for k, v in b.items()} for b in host]
    ch = tr["chunk"]
    chunks = [dev[i * ch:(i + 1) * ch] for i in range(tr["distinct_chunks"])]
    with ctx.span("check_chunk"):
        state, m0 = trainer.run(state, chunks[0])
    losses = np.asarray(m0["per_party"], np.float64)
    change = ref.change_norms(state.params["parties"],
                              lm_system.initial_parties(ctx, shapes))
    gc.collect()
    ctx.log(f"first chunk of {ch} steps run (compiles done)")

    pending, i, per_party = collections.deque(), 1, []
    with ctx.window():
        t_end = time.perf_counter() + ctx.window_seconds
        while time.perf_counter() < t_end:
            with ctx.span("chunk"):
                state, m = trainer.run(state, chunks[i % len(chunks)])
            per_party.append(m["per_party"])
            pending.append(m["loss"])
            if len(pending) > tr["queue_depth"]:
                pending.popleft().block_until_ready()
            i += 1
        jax.block_until_ready(state)
    rounds = (i - 1) * ch
    failed = sum(ch for p in jax.device_get(per_party)
                 if not np.all(np.isfinite(p)))

    def verify():
        nonlocal state, dev, chunks, m, pending
        state = dev = chunks = m = pending = None
        gc.collect()
        nums = readings({"loss": losses, "change": change},
                        reference(ctx, shapes, host))
        ctx.log("reference followed")
        return [Check(k, v, ctx.limits[k]) for k, v in nums.items()]

    return Outcome(
        attempted=rounds, failed=failed,
        metrics={"train_tokens_per_s":
                 rounds * tr["batch"] * tr["seq"] / ctx.window_s},
        counters={"rounds": rounds,
                  "model_flops_per_round": flops.lm_train_round(
                      ctx.config, tr["batch"], tr["seq"])},
        verify=verify)
