#!/usr/bin/env python3
"""Drive the EASTER federation's main paths once on a TPU and check them.

    python3 chip_smoke.py             # one chip: protocol, LM serve, LM train
    python3 chip_smoke.py --chips 4   # four chips: the sharded engine only

One chip runs three phases through the entry points a user calls:

  protocol  ``EasterClassifier``, C=4 heterogeneous MLP parties on a
            synthetic vertical split: a few training rounds (the loss is
            finite and falls), the vectorized engine against the loop
            oracle, the compiled Pallas ``blind_agg`` against the jnp
            aggregate, in-kernel (PRNG) masks against the unblinded mean,
            then one C=64 round on the float and the int8 wire.
  serve     ``qwen2-1.5b`` at its published widths in bf16, C=4,
            ``ServingEngine`` answering a small request stream on the
            float and the int8 wire, plus one blinded round checked
            against the plain mean of the raw party embeddings.
  train     ``qwen2-1.5b`` widths with depth cut, ``api.build_trainer``.

With ``--chips 4`` it runs only the sharded engine (one passive party
per chip) against the vectorized engine on one device, and checks that
the compiled programs gather over all four chips.

Weights are random (``init_params(PRNGKey(--seed))``) and data synthetic,
made from ``--seed``. Every phase prints one line of what it ran and
checked; a failed check raises, so the script exits non-zero and prints
no result. The last line of a passing run is one JSON object naming the
device. The script needs the repository's ``src/`` beside it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

LM_ARCH = "qwen2-1.5b"
EOS_ID = 151643                        # qwen2's <|endoftext|>
EPS32 = 2.0 ** -23


class CheckFailed(AssertionError):
    pass


def check(ok, what: str):
    if not ok:
        raise CheckFailed(what)


def _max_err(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def _tree_rel_err(a, b) -> float:
    """max |a - b| over a pytree, relative to max |b|."""
    import jax
    import numpy as np
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    err = max(_max_err(x, y) for x, y in zip(la, lb))
    scale = max(float(np.max(np.abs(np.asarray(y, np.float64))))
                for y in lb)
    return err / max(scale, 1e-30)


def _peak() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak / 2**30:.2f} GiB"


def _aot(fn, *args):
    """Compile ``fn`` (a jitted function) for ``args``; (compiled, s)."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _gathers_over(hlo: str, n: int) -> bool:
    """True when the HLO holds an all-gather whose group spans n devices."""
    iota = f"replica_groups=[1,{n}]"
    listed = re.compile(r"replica_groups=\{\{" + r"\d+," * (n - 1)
                        + r"\d+\}\}")
    return any("all-gather" in ln and (iota in ln or listed.search(ln))
               for ln in hlo.splitlines())


# ---------------------------------------------------------------------------
# protocol: the paper-scale classifier
# ---------------------------------------------------------------------------


# four MLP party shapes, cycled: party k runs MLP_WIDTHS[k % 4]. At C=64
# over a 784-feature split every (shape, slice width) group then holds 4
# or 12 parties, so each group lays out over four chips.
MLP_WIDTHS = [((256, 128), (128,)), ((128,), (64,)), ((512, 256), (256,)),
              ((192, 96), (96,))]


def _mlp_arches(C: int, d_embed: int, n_cls: int):
    from repro.core.party_models import PartyArch
    return [PartyArch("mlp", *MLP_WIDTHS[k % 4], d_embed, n_cls)
            for k in range(C)]


def _classifier(C, nf, n_cls, d_embed, wire="float", **kw):
    from repro.configs.base import EasterConfig
    from repro.core.protocol import EasterClassifier
    return EasterClassifier(
        EasterConfig(num_passive=C - 1, d_embed=d_embed, mask_mode=wire),
        _mlp_arches(C, d_embed, n_cls), nf, **kw)


def _split(x, C):
    import jax.numpy as jnp
    from repro.core.protocol import split_features
    return [jnp.asarray(v) for v in split_features(x, C)]


def _agg_bound(E_all, masks, wire: str) -> float:
    """What the blinded aggregate may differ from the plain mean by: fp32
    rounding of the blinded sums (float), the quantization step (int8)."""
    import numpy as np
    from repro.core import blinding
    C = E_all.shape[0]
    amax = float(np.max(np.abs(np.asarray(E_all, np.float32))))
    if wire == "int8":
        scale = float(blinding.ring_scale(amax, C, "int8"))
        return 0.5 / scale + 4 * EPS32 * amax
    mmax = float(np.max(np.abs(np.asarray(masks, np.float32))))
    return 2 * C * EPS32 * (amax + mmax)


def protocol_phase(seed: int, *, batch: int = 256, rounds: int = 8,
                   d_embed: int = 128, big_c: int = 64):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import blinding
    from repro.data import make_dataset
    from repro.kernels.blind_agg import make_prng_blind_agg, round_words

    C = 4
    ds = make_dataset("mnist_like", n_train=batch * rounds, n_test=batch,
                      seed=seed)
    xb = [ds.x_train[r * batch:(r + 1) * batch] for r in range(rounds)]
    yb = [jnp.asarray(ds.y_train[r * batch:(r + 1) * batch])
          for r in range(rounds)]
    nf = [v.shape[-1] for v in _split(ds.x_train[:1], C)]
    vec = _classifier(C, nf, ds.n_classes, d_embed)
    params = vec.init_params(jax.random.PRNGKey(seed))
    xs = [_split(x, C) for x in xb]

    # 1. a few training rounds on the production (vectorized) engine
    init_opt, step = vec.make_train_step("adam", 1e-3)
    opt = init_opt(params)
    # the first round compiles, and stacks the per-party params into the
    # groups' stacks that the step then carries from round to round
    t0 = time.perf_counter()
    p, opt, total, _ = step(params, opt, xs[0], yb[0], vec.masks(batch, 0))
    losses = [float(total)]
    t_c = time.perf_counter() - t0
    t0 = time.perf_counter()
    for r in range(1, rounds):
        p, opt, total, _ = step(p, opt, xs[r], yb[r], vec.masks(batch, r))
        losses.append(float(total))
    t_run = time.perf_counter() - t0
    check(np.all(np.isfinite(losses)), f"protocol losses {losses}")
    check(np.mean(losses[-2:]) < losses[0], f"loss did not fall: {losses}")
    print(f"[protocol] C={C} MLP parties, batch {batch}, d_embed {d_embed}: "
          f"{rounds} adam rounds, loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"(first round with compile {t_c:.1f} s, {rounds - 1} rounds "
          f"{t_run:.2f} s)")

    # 2. vectorized engine vs the loop oracle: one round's per-party
    # losses and every party's gradient, at full f32 matmul precision
    # (TPU's default f32 matmul rounds operands to bf16, which would
    # measure the precision setting, not the engines)
    loop = _classifier(C, nf, ds.n_classes, d_embed, engine="loop")

    def one_round(s):
        fn = jax.jit(jax.value_and_grad(s.loss_fn, has_aux=True))
        return fn(params, xs[0], yb[0], s.masks(batch, 0))

    with jax.default_matmul_precision("highest"):
        (_, per_v), g_v = one_round(vec)
        (_, per_l), g_l = one_round(loop)
    e_loss = _max_err(per_v, per_l) / float(np.max(np.abs(per_l)))
    e_grad = _tree_rel_err(g_v, g_l)
    check(e_loss < 1e-5 and e_grad < 1e-4,
          f"vectorized vs loop: loss rel {e_loss:.2e}, grad rel {e_grad:.2e}")
    print(f"[protocol] vectorized vs loop oracle (f32 highest): per-party "
          f"loss rel err {e_loss:.2e} (< 1e-5), grad rel err "
          f"{e_grad:.2e} (< 1e-4)")

    # 3. the compiled Pallas blind_agg against the jnp aggregate
    ker = _classifier(C, nf, ds.n_classes, d_embed, use_kernel=True)
    (_, per_k), g_k = one_round(ker)
    (_, per_j), g_j = one_round(vec)
    e_loss = _max_err(per_k, per_j) / float(np.max(np.abs(per_j)))
    e_grad = _tree_rel_err(g_k, g_j)
    _, kstep = ker.make_train_step("adam", 1e-3)
    kc, t_ck = _aot(kstep.__wrapped__, params, init_opt(params), xs[0],
                    yb[0], ker.masks(batch, 0))
    check("tpu_custom_call" in kc.as_text(), "blind_agg kernel not compiled")
    _, _, k_total, _ = kc(params, init_opt(params), xs[0], yb[0],
                          ker.masks(batch, 0))
    check(np.isfinite(float(k_total)), "kernel train round not finite")
    check(e_loss < 1e-5 and e_grad < 1e-5,
          f"kernel vs jnp: loss rel {e_loss:.2e}, grad rel {e_grad:.2e}")
    print(f"[protocol] use_kernel=True (compiled blind_agg, tpu_custom_call "
          f"in HLO) vs jnp aggregate: loss rel err {e_loss:.2e}, grad rel "
          f"err {e_grad:.2e} (< 1e-5); kernel step compile {t_ck:.1f} s")

    # 4. in-kernel PRNG masks: cancellation against the unblinded mean
    fused = _classifier(C, nf, ds.n_classes, d_embed, fused_masks=True)
    E_all = jax.jit(fused.local_embeds)(params, xs[0])
    agg = jax.jit(lambda e, r: fused.global_embed(e, blinding.FusedMasks(r)))
    hlo = agg.lower(E_all, jnp.int32(3)).compile().as_text()
    check("tpu_custom_call" in hlo, "PRNG blind_agg kernel not compiled")
    mean = np.mean(np.asarray(E_all, np.float32), axis=0)
    e_fused = _max_err(agg(E_all, jnp.int32(3)), mean)
    # fp32 accumulation of C embeddings and K(K-1) pair masks in
    # [-1/2, 1/2): n adds, each off by at most eps/2 of the running sum
    K = C - 1
    n_terms = C + K * (K - 1)
    amax = float(np.max(np.abs(np.asarray(E_all, np.float32))))
    bound = n_terms * EPS32 / 2 * (C * amax + K * (K - 1) / 2) / C
    # the same kernel with every sign +1: pair masks no longer cancel, so
    # the output shows the masks the PRNG really drew. Both endpoints of a
    # pair draw the same stream, so each pair adds 2u: std sqrt(12/12)/C
    # = 0.25 at K=3 (independent streams would give 0.18)
    eng = fused.mask_engine
    probe = make_prng_blind_agg(eng.seed_hi, eng.seed_lo,
                                np.ones_like(eng.signs))
    zeros = jnp.zeros((batch, d_embed), jnp.float32)
    zk = jnp.zeros((C - 1, batch, d_embed), jnp.float32)
    m0 = np.asarray(jax.jit(probe)(zeros, zk, round_words(0)))
    m1 = np.asarray(jax.jit(probe)(zeros, zk, round_words(1)))
    check(e_fused <= bound, f"fused masks: err {e_fused:.2e} > {bound:.2e}")
    check(m0.std() > 0.05 and _max_err(m0, m1) > 0.05,
          f"PRNG masks degenerate: std {m0.std():.3f}")
    (_, per_f), _ = one_round(fused)
    e_loss = _max_err(per_f, per_j) / float(np.max(np.abs(per_j)))
    check(e_loss < 1e-4, f"fused-mask round loss rel err {e_loss:.2e}")
    print(f"[protocol] fused_masks=True (in-kernel PRNG masks): |blinded "
          f"aggregate - unblinded mean| {e_fused:.2e} (<= {bound:.2e}); "
          f"uncancelled probe std {m0.std():.3f}, rounds differ; fused "
          f"train round loss rel err {e_loss:.2e}")

    # 5. one C=64 round on each wire
    nf64 = [v.shape[-1] for v in _split(ds.x_train[:1], big_c)]
    xs64 = _split(xb[0], big_c)
    for wire in ("float", "int8"):
        s = _classifier(big_c, nf64, ds.n_classes, d_embed, wire=wire)
        p64 = s.init_params(jax.random.PRNGKey(seed + 1))
        masks = s.masks(batch, 0)
        i_opt, st = s.make_train_step("adam", 1e-3)
        c64, t_c64 = _aot(st.__wrapped__, p64, i_opt(p64), xs64, yb[0], masks)
        _, _, total, per = c64(p64, i_opt(p64), xs64, yb[0], masks)
        check(np.isfinite(float(total)) and np.all(np.isfinite(per)),
              f"C={big_c} {wire} round not finite")
        E_all = jax.jit(s.local_embeds)(p64, xs64)
        E = jax.jit(s.global_embed)(E_all, masks)
        err = _max_err(E, np.mean(np.asarray(E_all, np.float32), 0))
        bound = _agg_bound(E_all, masks, wire)
        check(err <= bound, f"C={big_c} {wire}: err {err:.2e} > {bound:.2e}")
        print(f"[protocol] C={big_c} vectorized round, {wire} wire: loss "
              f"{float(total):.4f}, |aggregate - mean| {err:.2e} (<= "
              f"{bound:.2e}); compile {t_c64:.1f} s")
    print(f"[protocol] peak device memory {_peak()}")


# ---------------------------------------------------------------------------
# LM serving at full width
# ---------------------------------------------------------------------------


def _requests(seed: int, vocab: int, n: int, prompt_lens, budgets):
    import numpy as np
    from repro.core import api
    rng = np.random.default_rng(seed)
    lens = [prompt_lens[i % len(prompt_lens)] for i in range(n)]
    return [api.ServeRequest(
        tokens=tuple(int(t) for t in rng.integers(0, vocab, p)),
        max_new_tokens=int(rng.integers(budgets[0], budgets[1] + 1)),
        eos_id=EOS_ID) for p in lens]


def _serve_wire(sys_, params, wire: str, seed: int, *, lanes: int,
                max_len: int, chunk: int, reqs):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import aggregation, blinding, serving

    vocab = sys_.cfg.vocab_size
    eng = serving.ServingEngine(sys_, params, lanes=lanes, max_len=max_len,
                                chunk=chunk, base_key=seed)
    t0 = time.perf_counter()
    comps = eng.run(reqs)
    t_cold = time.perf_counter() - t0
    eng.reset()
    t0 = time.perf_counter()
    warm = eng.run(reqs)
    t_warm = time.perf_counter() - t0
    check(len(comps) == len(reqs), f"{len(comps)} of {len(reqs)} served")
    n_tok = 0
    for c in comps:
        toks = c.tokens
        budget = min(c.request.max_new_tokens,
                     max_len - len(c.request.tokens) + 1)
        stopped = bool(toks) and toks[-1] == EOS_ID
        check(len(toks) == budget or (stopped and len(toks) <= budget),
              f"{wire}: {len(toks)} tokens for budget {budget}")
        check(all(0 <= t < vocab for t in toks), f"{wire}: id out of vocab")
        n_tok += len(toks)
    same = sum(a.tokens == b.tokens for a, b in
               zip(sorted(comps, key=lambda c: c.nonce),
                   sorted(warm, key=lambda c: c.nonce)))

    # one blinded round on a prompt: the aggregate the chip computes from
    # blinded uplinks equals the plain mean of the raw party embeddings
    seeds = sys_.mask_seeds()
    rnd = blinding.PREFILL_DOMAIN + 5

    @jax.jit
    def round_check(params, tokens):
        E_all = jnp.stack([sys_.local_embed(params["parties"][k], pcfg,
                                            tokens)[0]
                           for k, pcfg in enumerate(sys_.party_cfgs)])
        masks = sys_.masks_for(E_all.shape[1:], rnd, seeds)
        if wire == "int8":
            E = aggregation.aggregate_ring(E_all, masks, "int8")
        else:
            E = aggregation.blind_and_aggregate(E_all, masks)
        logits = sys_.decide(params["parties"][0], sys_.party_cfgs[0],
                             E.astype(E_all.dtype))
        return E_all, masks, E, jnp.all(jnp.isfinite(logits))

    tokens = jnp.asarray(reqs[0].tokens, jnp.int32)[None]
    E_all, masks, E, finite = round_check(params, tokens)
    err = _max_err(E, np.mean(np.asarray(E_all, np.float32), 0))
    bound = _agg_bound(E_all, masks, wire)
    check(bool(finite), f"{wire}: NaN/inf logits")
    check(float(np.max(np.abs(np.asarray(masks, np.float32)))) > 0,
          f"{wire}: masks are all zero")
    check(err <= bound, f"{wire}: |aggregate - mean| {err:.2e} > {bound:.2e}")
    lens = sorted({len(r.tokens) for r in reqs})
    print(f"[serve] {wire} wire: {len(comps)} requests (prompts {lens}, "
          f"{n_tok} tokens) on {lanes} lanes, max_len {max_len}, chunk "
          f"{chunk}; budgets/EOS and vocab ids ok, logits finite; one "
          f"round |blinded aggregate - raw mean| {err:.2e} (<= {bound:.2e}); "
          f"cold run {t_cold:.1f} s (compiles), warm replay {t_warm:.2f} s, "
          f"{same}/{len(comps)} replays identical")


def serve_phase(seed: int, *, arch: str = LM_ARCH, lanes: int = 4,
                max_len: int = 512, chunk: int = 8, n_requests: int = 6,
                prompt_lens=(48, 160), budgets=(8, 20)):
    import jax
    from repro.configs.base import EasterConfig, get_config
    from repro.core.easter_lm import EasterLM

    cfg = get_config(arch)
    systems = {w: EasterLM(cfg=cfg, easter=EasterConfig(num_passive=3,
                                                        mask_mode=w))
               for w in ("float", "int8")}
    sys0 = systems["float"]
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(sys0.init_params)(jax.random.PRNGKey(seed)))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"[serve] {arch} {cfg.dtype}, C={sys0.C}, party depths "
          f"{[c.n_layers for c in sys0.party_cfgs]}, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}: {n / 1e9:.2f} B params from "
          f"init_params(PRNGKey({seed})) in {time.perf_counter() - t0:.1f} s")
    reqs = _requests(seed, cfg.vocab_size, n_requests, prompt_lens, budgets)
    for wire, s in systems.items():
        _serve_wire(s, params, wire, seed, lanes=lanes, max_len=max_len,
                    chunk=chunk, reqs=reqs)
    print(f"[serve] peak device memory {_peak()}")


# ---------------------------------------------------------------------------
# LM training at full width, cut depth
# ---------------------------------------------------------------------------


def train_phase(seed: int, *, arch: str = LM_ARCH, n_layers: int = 4,
                batch: int = 2, seq: int = 512, chunk: int = 2,
                chunks: int = 2):
    import jax
    import numpy as np
    from repro.configs.base import EasterConfig, get_config
    from repro.core import api
    from repro.core.easter_lm import EasterLM
    from repro.data.synthetic import lm_batch_iterator

    full = get_config(arch)
    cfg = dataclasses.replace(full, n_layers=n_layers)
    s = EasterLM(cfg=cfg, easter=EasterConfig(num_passive=3))
    params = jax.jit(s.init_params)(jax.random.PRNGKey(seed))
    trainer = api.build_trainer(s, api.TrainConfig(optimizer="sgd", lr=1e-2,
                                                   chunk=chunk))
    state = trainer.init(params)
    it = lm_batch_iterator(cfg.vocab_size, batch, seq, seed=seed)
    losses, times = [], []
    for _ in range(chunks):
        t0 = time.perf_counter()
        state, m = trainer.run(state, [next(it) for _ in range(chunk)])
        loss, per = np.asarray(m["loss"]), np.asarray(m["per_party"])
        times.append(time.perf_counter() - t0)
        check(loss.shape == (chunk,) and per.shape == (chunk, s.C),
              f"train metrics shapes {loss.shape} {per.shape}")
        check(np.all(np.isfinite(loss)) and np.all(np.isfinite(per)),
              f"train losses not finite: {loss} {per}")
        losses.extend(loss.tolist())
    print(f"[train] {arch} widths, depth cut {full.n_layers} -> {n_layers} "
          f"layers (passives {s.party_cfgs[1].n_layers}; at full depth the "
          f"trainer does not fit one 16 GB chip, and adam's fp32 moments do "
          f"not fit even at this depth), sgd, batch {batch} x seq {seq}, "
          f"{chunks} chunks of {chunk}: losses "
          f"{[round(x, 4) for x in losses]}, per-party finite; chunk times "
          f"{[round(t, 2) for t in times]} s (first compiles)")
    print(f"[train] peak device memory {_peak()}")


# ---------------------------------------------------------------------------
# four chips: the sharded engine, one passive party per chip
# ---------------------------------------------------------------------------


def sharded_lm_phase(seed: int, mesh, *, arch: str = LM_ARCH,
                     n_layers: int = 8, batch: int = 2, seq: int = 512,
                     chunk: int = 2, lanes: int = 4, max_len: int = 256,
                     decode_chunk: int = 8):
    """The sharded engine against the vectorized one on device 0.

    Each engine runs prefill, one decode chunk and one train chunk on its
    own copy of the same weights (``init_params`` of one key), and
    donates them to the train chunk, which runs last. The sharded engine
    lays out compute, not storage: every chip holds the whole
    federation's weights, replicated. The vectorized reference holds them
    on one chip, and it bounds the depth: by the compiler's count its
    train chunk takes 12.1 GiB of the chip's 14.7 at 8 layers and 13.8
    GiB at 16; 8 leaves room for the runtime's own buffers."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding
    from repro import optim
    from repro.configs.base import EasterConfig, get_config
    from repro.core import api, train_loop
    from repro.core.easter_lm import EasterLM
    from repro.data.synthetic import lm_batch_iterator

    full = get_config(arch)
    cfg = dataclasses.replace(full, n_layers=n_layers)
    easter = EasterConfig(num_passive=mesh.devices.size)
    sh = EasterLM(cfg=cfg, easter=easter, engine="sharded", mesh=mesh)
    vec = EasterLM(cfg=cfg, easter=easter)
    check(sh._shard_ok(), "passive stack does not lay out over the mesh")
    seeds = sh.mask_seeds()
    opt = optim.make_optimizer("sgd", 1e-2)
    it = lm_batch_iterator(cfg.vocab_size, batch, seq, seed=seed)
    batches = train_loop.stack_batches([next(it) for _ in range(chunk)])
    toks = jax.random.randint(jax.random.PRNGKey(seed + 2), (1, 64), 0,
                              cfg.vocab_size)
    reqs = _requests(seed, cfg.vocab_size, lanes, (32, 48),
                     (decode_chunk, decode_chunk))
    dcfg = api.DecodeConfig(lanes=lanes, max_len=max_len,
                            chunk=decode_chunk, donate=False)
    E, gen, train = {}, {}, {}
    for name, s in (("sharded", sh), ("vectorized", vec)):
        placed = (NamedSharding(mesh, P()) if name == "sharded"
                  else SingleDeviceSharding(jax.devices()[0]))
        params = jax.jit(s.init_params, out_shardings=placed)(
            jax.random.PRNGKey(seed))
        # serving: the prefill aggregate, then one decode chunk
        pf = jax.jit(lambda p, t, c, s=s: s.prefill(p, t, c, seeds=seeds,
                                                    round_idx=7)[0])
        E[name] = np.asarray(pf(params, toks, s.init_caches(1, max_len)),
                             np.float32)
        prefill_fn, decode_fn = api.build_decoder(s, dcfg)
        state = api.init_decode_state(s, dcfg)
        for lane, r in enumerate(reqs):
            state = prefill_fn(params, state, r, lane, nonce=lane)
        dec, t_d = _aot(decode_fn, params, state)
        if name == "sharded":
            check(_gathers_over(dec.as_text(), mesh.devices.size),
                  "sharded decode chunk has no all-gather over the mesh")
        buf = np.asarray(dec(params, state)[0])
        check(np.all((buf >= 0) & (buf < cfg.vocab_size)),
              f"{name}: decoded id outside the vocab")
        gen[name] = buf
        del state
        # training: one chunk, donating the weights (not used again)
        fn = train_loop.build_train_chunk(s, opt)
        opt_state = opt.init(params)
        tc, t_c = _aot(fn, params, opt_state, batches, jnp.int32(0))
        if name == "sharded":
            check(_gathers_over(tc.as_text(), mesh.devices.size),
                  "sharded train chunk has no all-gather over the mesh")
        mem = tc.memory_analysis()
        train[name] = np.asarray(tc(params, opt_state, batches,
                                    jnp.int32(0))[3]["per_party"])
        del params
        gc.collect()
        print(f"[sharded] {name}: decode chunk compile {t_d:.1f} s; train "
              f"chunk compile {t_c:.1f} s, per device "
              f"{mem.argument_size_in_bytes / 2**30:.2f} GiB arguments + "
              f"{mem.temp_size_in_bytes / 2**30:.2f} GiB temporaries, "
              f"per-party losses "
              f"{np.array2string(train[name], precision=4)}; "
              f"peak {_peak()}")
    check(np.all(np.isfinite(train["sharded"])), "sharded losses not finite")
    e_train = float(np.max(np.abs(train["sharded"] - train["vectorized"])
                           / np.abs(train["vectorized"])))
    e_pre = _max_err(E["sharded"], E["vectorized"]) / float(
        np.max(np.abs(E["vectorized"])))
    check(e_train < 1e-2, f"sharded vs vectorized train rel {e_train:.2e}")
    check(e_pre < 1e-2, f"sharded vs vectorized prefill rel {e_pre:.2e}")
    agree = float(np.mean(gen["sharded"] == gen["vectorized"]))
    print(f"[sharded] {arch} widths, depth cut {full.n_layers} -> {n_layers} "
          f"(passives {sh.party_cfgs[1].n_layers}), C={sh.C}, one passive "
          f"party per chip: _shard_ok() true, all-gather over "
          f"{mesh.devices.size} devices in the train and decode HLO; "
          f"per-party train loss rel err vs vectorized {e_train:.2e} "
          f"(< 1e-2), prefill aggregate rel err {e_pre:.2e} (< 1e-2), "
          f"decode chunk tokens agree {agree:.2%}")


def sharded_classifier_phase(seed: int, mesh, *, C: int = 64,
                             batch: int = 256, d_embed: int = 128):
    import jax
    import numpy as np
    from repro.data import make_dataset

    ds = make_dataset("mnist_like", n_train=batch, n_test=batch, seed=seed)
    nf = [v.shape[-1] for v in _split(ds.x_train[:1], C)]
    xs = _split(ds.x_train, C)
    y = jax.numpy.asarray(ds.y_train)
    sh = _classifier(C, nf, ds.n_classes, d_embed, engine="sharded",
                     mesh=mesh)
    vec = _classifier(C, nf, ds.n_classes, d_embed)
    sizes = [len(idx) for _, idx in sh._eng.groups]
    check(all(sh._eng._sharded(n) for n in sizes),
          f"groups {sizes} do not all lay out over the party axis")
    params = vec.init_params(jax.random.PRNGKey(seed))
    res = {}
    with jax.default_matmul_precision("highest"):
        for name, s in (("sharded", sh), ("vectorized", vec)):
            fn = jax.jit(jax.value_and_grad(s.loss_fn, has_aux=True))
            masks = s.masks(batch, 0)
            if name == "sharded":
                hlo = fn.lower(params, xs, y, masks).compile().as_text()
                check(_gathers_over(hlo, mesh.devices.size),
                      "sharded classifier round has no all-gather")
            res[name] = fn(params, xs, y, masks)
    (_, per_s), g_s = res["sharded"]
    (_, per_v), g_v = res["vectorized"]
    e_loss = _max_err(per_s, per_v) / float(np.max(np.abs(per_v)))
    e_grad = _tree_rel_err(g_s, g_v)
    check(e_loss < 1e-5 and e_grad < 1e-4,
          f"sharded classifier: loss rel {e_loss:.2e}, grad rel {e_grad:.2e}")
    print(f"[sharded] classifier C={C}, party groups {sizes} all laid over "
          f"{mesh.devices.size} devices, all-gather in HLO; vs vectorized "
          f"(f32 highest): loss rel err {e_loss:.2e} (< 1e-5), grad rel err "
          f"{e_grad:.2e} (< 1e-4)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded engine over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX found platform {backend!r} "
                 f"({jax.devices()[0].device_kind}); this check runs only "
                 f"on a TPU")
    devs = jax.devices()
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devs)} device(s)")
    kind = devs[0].device_kind
    print(f"device: {devs[0].platform} {kind} x{len(devs)}; jax "
          f"{jax.__version__}; compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        from repro.launch.mesh import make_party_mesh
        mesh = make_party_mesh(4)
        sharded_classifier_phase(args.seed, mesh)
        sharded_lm_phase(args.seed, mesh)
    else:
        # each phase's arrays die with it; collect so the next phase finds
        # the HBM free (the serve weights alone take 6.6 GB)
        for phase in (protocol_phase, serve_phase, train_phase):
            phase(args.seed)
            gc.collect()
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s; peak "
          f"device memory {_peak()}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
