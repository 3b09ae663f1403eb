"""Closed loop of EASTER classifier training rounds.

One round is what ``EasterClassifier`` runs per optimizer step: every
party's embedding, mask synthesis (``masks()``), blinding, aggregation,
every party's decision and loss, backward and update. Set-up builds the
compiled step with its state, drives it through the cell's first
``check_steps`` rounds (which compile everything the window uses) and hands
that same state to the window, which runs rounds back to back for the
cell's seconds. After the window the plain reference
(``bench/reference/classifier.py``) follows the same first rounds from the
same weights and data.

Configuration keys: ``parties``, ``party_mlps``, ``d_embed``,
``n_classes``, ``features``, ``wire``, ``optimizer``. Traffic keys:
``engine``, ``batch``, ``distinct_batches``, ``check_steps``,
``queue_depth``.
"""
from __future__ import annotations

import collections
import time


def build(ctx):
    """The system under test and the cell's inputs, from the files."""
    import jax
    from repro.configs.base import EasterConfig
    from repro.core.party_models import PartyArch
    from repro.core.protocol import EasterClassifier
    from bench import gen

    cfg, tr = ctx.config, ctx.traffic
    C, D = cfg["parties"], cfg["d_embed"]
    mlps = cfg["party_mlps"]
    arches = [PartyArch("mlp", tuple(mlps[k % len(mlps)]["embed"]),
                        tuple(mlps[k % len(mlps)]["decide"]), D,
                        cfg["n_classes"]) for k in range(C)]
    nf = gen.split_sizes(cfg["features"]["n_features"], C)
    mesh = None
    if tr["engine"] == "sharded":
        from repro.launch.mesh import make_party_mesh
        mesh = make_party_mesh(ctx.chips)
    system = EasterClassifier(
        EasterConfig(num_passive=C - 1, d_embed=D, mask_mode=cfg["wire"]),
        arches, nf, engine=tr["engine"], mesh=mesh)
    shapes = jax.eval_shape(system.init_params, jax.random.PRNGKey(0))
    return system, shapes


def rows(ctx):
    """(x, y) host arrays of every batch the cell feeds, from the seed."""
    from bench import gen
    f, tr = ctx.config["features"], ctx.traffic
    n = tr["batch"] * tr["distinct_batches"]
    return gen.mixture_rows(
        ctx.rng(2), n, n_features=f["n_features"],
        n_classes=ctx.config["n_classes"], image_hw=f["image_hw"],
        n_groups=f["groups"], moduli=f["moduli"], sep=f["sep"],
        noise=f["noise"])


def batches(ctx, x, y):
    """Per-round host batches: (per-party slices, labels)."""
    from bench import gen
    B, C = ctx.traffic["batch"], ctx.config["parties"]
    return [(gen.split_columns(x[i * B:(i + 1) * B], C), y[i * B:(i + 1) * B])
            for i in range(ctx.traffic["distinct_batches"])]


def readings(prog: dict, ref: dict) -> dict:
    """The numbers compared: program side against the reference."""
    from bench import compare
    ref_g = compare.flat(ref["grad1"])
    return {
        "loss_gap": compare.loss_gap(prog["loss"], ref["loss"]),
        "grad_gap": compare.norm_gap(compare.flat(prog["grad1"]), ref_g),
        "change_gap": compare.norm_gap(compare.flat(prog["change"]),
                                       compare.flat(ref["change"]),
                                       compare.moved_leaves(ref_g)),
    }


def reference(ctx, shapes, host_batches, *, dtype="float32", rows=None,
              lr=None):
    """The plain reference over the first ``check_steps`` rounds.

    ``rows`` keeps only that many rows of each batch and ``lr`` replaces
    the learning rate (planted faults)."""
    from bench import weights
    from bench.reference import classifier as ref
    o = ctx.config["optimizer"]
    n = ctx.traffic["check_steps"]
    b = [([x[:rows] for x in xs], y[:rows]) for xs, y in host_batches[:n]]
    return ref.follow(weights.fill(shapes, ctx.key(1)), b,
                      lr=o["lr"] if lr is None else lr, b1=o["b1"],
                      b2=o["b2"], eps=o["eps"], dtype=dtype)


def control(ctx, mode: str) -> dict:
    """The numbers compared when something else stands in the program's
    place: ``control`` (the reference in bfloat16), ``half_batch`` (the
    reference fed only the first half of every batch), ``unchanged`` (a
    step that hands back its state as it got it) or ``no_exchange`` (the
    program without its exchange between chips)."""
    _, shapes = build(ctx)
    host = batches(ctx, *rows(ctx))
    ref = reference(ctx, shapes, host)
    if mode == "control":
        prog = reference(ctx, shapes, host, dtype="bfloat16")
    elif mode == "half_batch":
        prog = reference(ctx, shapes, host, rows=ctx.traffic["batch"] // 2)
    elif mode == "unchanged":
        # every step's loss is taken at the initial parameters, and the
        # optimizer's state stays empty
        frozen = reference(ctx, shapes, host, lr=0.0)
        prog = {"loss": frozen["loss"],
                "grad1": [[0.0] * len(p) for p in ref["grad1"]],
                "change": [[0.0] * len(p) for p in ref["change"]]}
    elif mode == "no_exchange":
        return _without_exchange(ctx)
    else:
        raise ValueError(f"no control mode {mode!r}")
    return readings(prog, ref)


def _without_exchange(ctx) -> dict:
    """The program with the party-axis exchange left out: every chip's
    all-gather hands back its own shard in place of the others'."""
    import jax
    import jax.numpy as jnp
    real = jax.lax.all_gather

    def local(x, axis_name, *, axis=0, tiled=False, **kw):
        n = jax.lax.axis_size(axis_name)
        if tiled:
            return jnp.concatenate([x] * n, axis=axis)
        return jnp.stack([x] * n, axis=axis)

    jax.lax.all_gather = local
    try:
        out = run(ctx)
    finally:
        jax.lax.all_gather = real
    return {c.name: c.value for c in out.verify()}


def run(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import flops, weights
    from bench.harness import Check, Outcome

    cfg, tr = ctx.config, ctx.traffic
    B, nb, n_check = tr["batch"], tr["distinct_batches"], tr["check_steps"]
    system, shapes = build(ctx)
    ctx.log("federation built (key ceremony done)")
    params = weights.fill(shapes, ctx.key(1))
    host = batches(ctx, *rows(ctx))
    ctx.log("weights and rows made")
    xs = [[jnp.asarray(s) for s in b[0]] for b in host]
    ys = [jnp.asarray(b[1]) for b in host]
    o = cfg["optimizer"]
    init_opt, step = system.make_train_step(o["name"], o["lr"], b1=o["b1"],
                                            b2=o["b2"], eps=o["eps"])
    opt = init_opt(params)
    p0, losses, opt1 = params, [], None
    with ctx.span("check_steps"):
        for r in range(n_check):
            params, opt, _, per = step(params, opt, xs[r], ys[r],
                                       system.masks(B, r))
            losses.append(per)
            if r == 0:
                opt1 = opt
    p_check = jax.block_until_ready(params)
    ctx.log(f"{n_check} checked rounds run (compiles done)")

    totals, pending, r = [], collections.deque(), n_check
    with ctx.window():
        t_end = time.perf_counter() + ctx.window_seconds
        while time.perf_counter() < t_end:
            with ctx.span("masks"):
                m = system.masks(B, r)
            with ctx.span("step"):
                params, opt, total, _ = step(params, opt, xs[r % nb],
                                             ys[r % nb], m)
            totals.append(total)
            pending.append(total)
            if len(pending) > tr["queue_depth"]:
                pending.popleft().block_until_ready()
            r += 1
        jax.block_until_ready((params, opt))
    rounds = r - n_check
    failed = int(np.sum(~np.isfinite(np.asarray(jax.device_get(totals)))))

    def verify():
        nonlocal params, opt, xs, ys, m, pending
        b1 = o["b1"]
        leaves = lambda t: [np.asarray(a, np.float64)  # noqa: E731
                            for a in jax.tree.leaves(t)]
        prog = {
            "loss": np.asarray(jax.device_get(losses), np.float64),
            "grad1": [[float(np.linalg.norm(a / (1 - b1)))
                       for a in leaves(s["m"])] for s in opt1],
            "change": [[float(np.linalg.norm(a - b))
                        for a, b in zip(leaves(pc), leaves(pi))]
                       for pc, pi in zip(p_check, p0)]}
        params = opt = xs = ys = m = pending = None
        nums = readings(prog, reference(ctx, shapes, host))
        ctx.log("reference followed")
        return [Check(k, v, ctx.limits[k]) for k, v in nums.items()]

    return Outcome(
        attempted=rounds, failed=failed,
        metrics={"round_ms": ctx.window_s / rounds * 1e3},
        counters={"rounds": rounds,
                  "model_flops_per_round": flops.classifier_round(cfg, B),
                  "uplink_bytes_per_round": system.bytes_per_round(B)},
        verify=verify)
