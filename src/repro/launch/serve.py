"""Serving launcher: EASTER continuous-batching serve tier.

Single-shot batched generation (R identical lanes, one request each):
    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --smoke \
        --batch 4 --prompt-len 32 --gen 32

Request-stream serving (continuous batching + EOS early-exit):
    PYTHONPATH=src python -m repro.launch.serve --smoke --requests 8 \
        --poisson

Both modes run on the typed serving surface (core/api.py): requests are
``ServeRequest``s admitted into decode slots by the ``ServingEngine``
scheduler (core/serving.py); every decoded token is ONE blinded protocol
round shared by all live lanes, with per-lane PRF nonces
(``blinding.serve_round``) and lane freezing after EOS. ``--step-loop``
keeps the pre-scan single-stream driver (one jitted serve_step dispatch
per token) for A/B timing and as the bit-exactness oracle
(tests/test_decode_scan.py).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import EasterConfig, get_config, smoke_variant
from repro.core import api, decode as decode_mod, serving
from repro.core.easter_lm import EasterLM
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode lanes (R concurrent requests per round)")
    ap.add_argument("--prompt-len", type=int, default=0,
                    help="0 = 32, or 8 with --smoke")
    ap.add_argument("--gen", type=int, default=0,
                    help="0 = 32, or 8 with --smoke")
    ap.add_argument("--num-passive", type=int, default=3)
    ap.add_argument("--d-embed", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--engine", default="vectorized",
                    choices=["vectorized", "sharded", "loop"],
                    help="passive-party execution: grouped vmap | grouped "
                         "vmap laid over a party mesh axis | seed loop")
    ap.add_argument("--party-devices", type=int, default=0,
                    help="party-axis mesh size for --engine sharded "
                         "(0 = all local devices)")
    ap.add_argument("--requests", type=int, default=0,
                    help="serve a stream of N requests through the "
                         "continuous-batching scheduler (mixed lengths, "
                         "EOS early-exit) instead of one fixed batch")
    ap.add_argument("--poisson", action="store_true",
                    help="open-loop Poisson arrivals for --requests "
                         "(otherwise all requests arrive at t=0)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate in requests/s "
                         "(0 = saturating: mean interarrival = 1ms)")
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode rounds per dispatch = scheduling quantum")
    ap.add_argument("--eos-id", type=int, default=7,
                    help="EOS token id for --requests mode (-1 disables "
                         "early exit)")
    ap.add_argument("--step-loop", action="store_true",
                    help="drive decode one jitted serve_step at a time "
                         "(the pre-scan path; A/B reference for the "
                         "fused lane engine)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    args.prompt_len = args.prompt_len or (8 if args.smoke else 32)
    args.gen = args.gen or (8 if args.smoke else 32)
    mesh = None
    if args.engine == "sharded":
        from repro.launch.mesh import make_party_mesh, require_party_layout
        mesh = make_party_mesh(args.party_devices or None)
        require_party_layout(mesh, args.num_passive)
        print(f"party mesh: {mesh}")
    sys_ = EasterLM(cfg=cfg, easter=EasterConfig(
        num_passive=args.num_passive, d_embed=args.d_embed),
        engine=args.engine, mesh=mesh)
    params = sys_.init_params(jax.random.PRNGKey(args.seed))

    if args.requests > 0:
        _serve_stream(args, cfg, sys_, params)
    elif args.step_loop:
        _single_batch_step_loop(args, cfg, sys_, params)
    else:
        _single_batch(args, cfg, sys_, params)


def _mk_requests(args, cfg):
    """Mixed short/long workload: prompts around --prompt-len, budgets
    around --gen (some lanes EOS out early when --eos-id >= 0). Prompt
    lengths are drawn from a few fixed buckets — each distinct length
    compiles one prefill program, so an unbucketed draw would pay
    O(requests) compiles."""
    rng = np.random.default_rng(args.seed)
    step = max(2, args.prompt_len // 4)
    buckets = sorted({max(2, b) for b in
                      range(step, args.prompt_len + 1, step)})
    reqs = []
    for _ in range(args.requests):
        plen = int(rng.choice(buckets))
        gen = max(1, int(rng.integers(max(1, args.gen // 4),
                                      args.gen + 1)))
        reqs.append(api.ServeRequest(
            tokens=tuple(int(t) for t in
                         rng.integers(0, cfg.vocab_size, size=plen)),
            max_new_tokens=gen, eos_id=args.eos_id,
            temperature=args.temperature))
    if args.poisson:
        rate = args.rate if args.rate > 0 else 1000.0
        arrivals = np.cumsum(rng.exponential(1.0 / rate,
                                             size=args.requests))
    else:
        arrivals = np.zeros(args.requests)
    return reqs, arrivals.tolist()


def _serve_stream(args, cfg, sys_, params):
    lanes = min(args.batch, args.requests)
    max_len = args.prompt_len + args.gen
    eng = serving.ServingEngine(sys_, params, lanes=lanes,
                                max_len=max_len, chunk=args.chunk,
                                base_key=args.seed)
    reqs, arrivals = _mk_requests(args, cfg)
    t0 = time.perf_counter()
    comps = eng.run(reqs, arrivals=arrivals)
    wall = time.perf_counter() - t0
    lat = sorted(c.latency_s for c in comps)
    toks = sum(len(c.tokens) for c in comps)
    p50 = lat[len(lat) // 2] * 1e3
    p99 = lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3
    print(f"served {len(comps)} requests on {lanes} lanes "
          f"(chunk={args.chunk}, {'poisson' if args.poisson else 'batch'} "
          f"arrivals) [incl. compile]")
    print(f"  {toks} tokens in {wall * 1e3:.1f} ms "
          f"({toks / wall:.1f} tok/s aggregate), "
          f"{eng.rounds_run} protocol rounds over {eng.chunks_run} chunks")
    print(f"  latency p50 {p50:.1f} ms   p99 {p99:.1f} ms")
    first = min(comps, key=lambda c: c.nonce)
    print(f"  sample (nonce 0): {len(first.tokens)} toks "
          f"{first.tokens[:12]} ...")


def _single_batch(args, cfg, sys_, params):
    """R identical-shape requests, one per lane, through the lane engine."""
    dcfg = api.DecodeConfig(lanes=args.batch,
                            max_len=args.prompt_len + args.gen,
                            chunk=args.gen, base_key=args.seed)
    prefill_fn, decode_fn = api.build_decoder(sys_, dcfg)
    state = api.init_decode_state(sys_, dcfg)
    key = jax.random.PRNGKey(args.seed + 1)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    t0 = time.perf_counter()
    for lane in range(args.batch):
        req = api.ServeRequest(
            tokens=tuple(int(t) for t in np.asarray(prompt[lane])),
            max_new_tokens=args.gen, eos_id=-1,
            temperature=args.temperature)
        state = prefill_fn(params, state, req, lane, nonce=lane)
    jax.block_until_ready(state.pos)
    t_prefill = time.perf_counter() - t0
    t0 = time.perf_counter()
    gen_toks, state, steps = decode_fn(params, state)
    jax.block_until_ready(gen_toks)
    dt = time.perf_counter() - t0
    seq = np.concatenate([np.asarray(prompt), np.asarray(gen_toks)], 1)
    B = args.batch
    print(f"prefill {args.prompt_len} tok x{B}: {t_prefill * 1e3:.1f} ms")
    print(f"decode  {int(steps)} steps x{B}: {dt * 1e3:.1f} ms "
          f"({B * int(steps) / dt:.1f} tok/s) "
          f"[lane engine (1 dispatch, state donated; incl. compile)]")
    print("sample token ids (first row):", seq[0, :24].tolist(), "...")


def _single_batch_step_loop(args, cfg, sys_, params):
    """The pre-scan A/B oracle: one jitted serve_step dispatch per token."""
    seeds = sys_.mask_seeds()
    key = jax.random.PRNGKey(args.seed + 1)
    B = args.batch
    total = args.prompt_len + args.gen
    prompt = jax.random.randint(key, (B, args.prompt_len), 0,
                                cfg.vocab_size)
    caches = sys_.init_caches(B, total)
    t0 = time.perf_counter()
    # per-request nonce: fresh-mask prefills must never share a round
    prefill = jax.jit(lambda p, t, c, n: sys_.prefill(p, t, c, seeds=seeds,
                                                      round_idx=n))
    _, caches = prefill(params, prompt, caches,
                        jnp.asarray(args.seed, jnp.int32))
    jax.block_until_ready(jax.tree.leaves(caches)[0])
    t_prefill = time.perf_counter() - t0

    tok = prompt[:, -1:]
    pos = jnp.asarray(args.prompt_len - 1, jnp.int32)
    serve = jax.jit(lambda p, t, c, po, k: _serve_sample_step(
        sys_, p, t, c, po, k, seeds, args.temperature))
    out = []
    t0 = time.perf_counter()
    for _ in range(args.gen):
        tok, caches, key = serve(params, tok, caches, pos, key)
        pos = pos + 1
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    gen_toks = jnp.concatenate(out, axis=1)
    seq = np.asarray(jnp.concatenate([prompt, gen_toks], axis=1))
    print(f"prefill {args.prompt_len} tok x{B}: {t_prefill * 1e3:.1f} ms")
    print(f"decode  {args.gen} steps x{B}: {dt * 1e3:.1f} ms "
          f"({B * args.gen / dt:.1f} tok/s) "
          f"[step-loop ({args.gen} jit dispatches)]")
    print("sample token ids (first row):", seq[0, :24].tolist(), "...")


def _serve_sample_step(sys_, params, tok, caches, pos, key, seeds,
                       temperature):
    """One pre-scan decode dispatch: serve_step + the shared sampling op
    (decode.sample_token — the same definition the fused engines use, so
    the drivers are comparable token-for-token)."""
    logits, caches = sys_.serve_step(params, tok, caches, pos, seeds)
    key, sub = jax.random.split(key)
    tok = decode_mod.sample_token(logits[:, -1], sub, temperature)
    return tok, caches, key


if __name__ == "__main__":
    enable_compile_cache()
    main()
