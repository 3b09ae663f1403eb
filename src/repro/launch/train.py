"""Training launcher: EASTER multi-party LM training end-to-end.

CPU example (reduced config):
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
        --steps 50 --batch 4 --seq 64
Production mesh usage mirrors the dry-run (see launch/dryrun.py); on real
TPU hardware drop --smoke and pass --mesh data,model.

Training runs through the typed training surface (``core/api.py``):
``build_trainer(sys, TrainConfig)`` wraps the fused scan-train engine
(core/train_loop.py) — every ``--chunk`` optimizer steps are ONE
compiled program with ``TrainState`` (params, optimizer state, step) as
the single carried object, the step doubling as the TRAIN-domain PRF
round counter. ``--chunk 1`` keeps the pre-scan driver (one jitted
train-step dispatch per round) behind the SAME ``Trainer.run`` call, for
A/B timing and as the bit-exactness oracle the fused path is tested
against (tests/test_train_chunk.py).

Heterogeneous per-party optimization (paper §IV-E) comes from
``--party-optimizers``, e.g. ``0=sgd:0.01,1=adagrad:0.005`` — parsed
into ``TrainConfig.party_optimizers``; unlisted parties fall back to
``--optimizer``/``--lr``; the per-party states ride the same checkpoint
as the params.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro import checkpoint, optim
from repro.configs.base import EasterConfig, get_config, smoke_variant
from repro.core import api
from repro.core.easter_lm import EasterLM
from repro.data.synthetic import lm_batch_iterator
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--party-optimizers", default=None,
                    help="heterogeneous per-party optimizers (paper "
                         "§IV-E), e.g. '0=sgd:0.01,1=adagrad:0.005' "
                         "(k=name:lr[:hparam=v...]); unlisted parties "
                         "fall back to --optimizer/--lr")
    ap.add_argument("--chunk", type=int, default=8,
                    help="fused scan training: optimizer steps per "
                         "compiled dispatch (core/train_loop.py); 1 = "
                         "step-at-a-time driver (the A/B oracle)")
    ap.add_argument("--num-passive", type=int, default=3)
    ap.add_argument("--d-embed", type=int, default=128)
    ap.add_argument("--mask-mode", "--wire", dest="mask_mode",
                    default="float",
                    choices=["float", "int32", "int8"],
                    help="wire format: float (paper) | int32 ring | int8 "
                         "narrow ring (quantized blinded uplink, ~4x "
                         "fewer bytes/round)")
    ap.add_argument("--no-easter", action="store_true")
    ap.add_argument("--grad-mode", default="easter",
                    choices=["easter", "joint"])
    ap.add_argument("--engine", default="vectorized",
                    choices=["vectorized", "sharded", "loop"],
                    help="passive-party execution: grouped vmap | grouped "
                         "vmap laid over a party mesh axis | seed loop")
    ap.add_argument("--party-devices", type=int, default=0,
                    help="party-axis mesh size for --engine sharded "
                         "(0 = all local devices)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="restore params/opt state from --ckpt if present")
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="checkpoint cadence in steps (with --chunk > 1, "
                         "saves on the first chunk boundary past it)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    easter = EasterConfig(num_passive=args.num_passive,
                          d_embed=args.d_embed, mask_mode=args.mask_mode,
                          enabled=not args.no_easter)
    mesh = None
    if args.engine == "sharded":
        from repro.launch.mesh import make_party_mesh, require_party_layout
        mesh = make_party_mesh(args.party_devices or None)
        require_party_layout(mesh, args.num_passive)
        print(f"party mesh: {mesh}")
    sys_ = EasterLM(cfg=cfg, easter=easter, grad_mode=args.grad_mode,
                    engine=args.engine, mesh=mesh)
    print(f"arch={cfg.name} parties={sys_.C} engine={args.engine} "
          f"party_depths={[c.n_layers for c in sys_.party_cfgs]} "
          f"d_embed={easter.d_embed}")

    params = sys_.init_params(jax.random.PRNGKey(args.seed))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"total params (all parties): {n:,}")

    tcfg = api.TrainConfig(
        optimizer=args.optimizer, lr=args.lr, chunk=args.chunk,
        party_optimizers=(optim.parse_party_spec(args.party_optimizers)
                          if args.party_optimizers else None))
    trainer = api.build_trainer(sys_, tcfg)
    if tcfg.party_optimizers:
        print(f"party optimizers: {trainer.opt.name}")
    state = trainer.init(params)
    start_step = 0
    if args.resume and args.ckpt and os.path.exists(args.ckpt):
        (restored, step0) = checkpoint.restore(
            args.ckpt, {"params": state.params, "opt": state.opt_state})
        start_step = step0 or 0
        state = api.TrainState(restored["params"], restored["opt"],
                               jax.numpy.asarray(start_step,
                                                 jax.numpy.int32))
        print(f"resumed from {args.ckpt} at step {start_step}")

    it = lm_batch_iterator(cfg.vocab_size, args.batch, args.seq,
                           seed=args.seed)
    t0 = time.perf_counter()
    history = []
    end = start_step + args.steps
    chunk = max(1, args.chunk)

    def log_steps(i0, losses, pers):
        # tok/s over steps completed SINCE (RE)START: the absolute step
        # index used to inflate throughput after --resume (t0 restarts,
        # the index doesn't)
        dt = time.perf_counter() - t0
        tok_s = (i0 + len(losses) - start_step) * args.batch * args.seq / dt
        for j in range(len(losses)):
            i = i0 + j
            if i % args.log_every == 0 or i == end - 1:
                loss = float(losses[j])
                per = np.round(np.asarray(pers[j]), 4)
                print(f"step {i:5d} loss {loss:9.4f} per-party {per} "
                      f"({tok_s:,.0f} tok/s)")
                history.append({"step": i, "loss": loss,
                                "per_party": per.tolist()})

    # ONE driver for both the fused-chunk path (chunk > 1: N steps per
    # dispatch, TrainState donated — rebound to the returned state) and
    # the step-at-a-time A/B oracle (chunk == 1) — Trainer.run hides the
    # carry plumbing either way.
    i = start_step
    while i < end:
        n_steps = min(chunk, end - i)
        state, metrics = trainer.run(
            state, [next(it) for _ in range(n_steps)])
        log_steps(i, np.asarray(metrics["loss"]),
                  np.asarray(metrics["per_party"]))
        i += n_steps
        if args.ckpt and (i // args.ckpt_every
                          != (i - n_steps) // args.ckpt_every):
            checkpoint.save(args.ckpt, {"params": state.params,
                                        "opt": state.opt_state}, step=i)
    if args.ckpt:
        checkpoint.save(args.ckpt,
                        {"params": state.params, "opt": state.opt_state},
                        step=end)
        print(f"checkpoint -> {args.ckpt}")
    out = {"arch": cfg.name, "history": history}
    os.makedirs("experiments/train", exist_ok=True)
    with open(f"experiments/train/{cfg.name}_train.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
