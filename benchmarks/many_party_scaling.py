"""Many-party scaling: protocol round time vs C for the party engines.

The paper stops at C = 4; the vectorized party engine (core/party_engine.py)
exists to push the same protocol to C = 128+. This benchmark sweeps
C in {4, 16, 64, 128} and times one jitted EASTER training round
(embed -> blind -> aggregate -> decide -> per-party grads -> update) on
synthetic vertically-split features, comparing:

  * engine=vectorized — grouped-vmap engine (O(#groups) XLA ops) + the
                        batched MaskEngine (O(1) traced mask-synthesis ops)
  * engine=sharded    — grouped-vmap engine laid out over a "party" mesh
                        axis with shard_map (needs >1 local device, e.g.
                        XLA_FLAGS=--xla_force_host_platform_device_count=4)
  * engine=loop       — the seed's per-party Python loop (O(C) ops) and the
                        O(C^2) pairwise mask loop;
                        skipped above --loop-max-c (trace time explodes)
  * --use-kernel      — fused Pallas blind_agg aggregation (K-tiled,
                        custom VJP) instead of the jnp reference
  * --fused-masks     — synthesize masks INSIDE the Pallas kernel
                        (pltpu PRNG; MaskEngine fallback off-TPU)

Every row also reports per-round mask-synthesis cost: ``mask_first_ms``
(trace + compile + first run — the loop oracle's O(K^2) host-side trace
cost lands here) and ``mask_ms`` (steady-state jitted synthesis with a
fresh round index). ``--mask-only`` skips the training-round timing, for
sweeping mask synthesis to C=128 on both engines cheaply.

``--save`` writes the tracked perf-dashboard document (schema
``easter/many-party-bench/v2``): per-C round/mask timings + wire
bytes/round, a fused scan-decode throughput row (``kind="decode"``:
``decode_ms_per_tok`` / ``tokens_per_s`` of the lane-batched decode
engine behind ``core/api.build_decoder``, core/decode.py, at LLM smoke
scale — the raw engine number), a continuous-batching serve-tier row
(``kind="serve"``: ``serve_ms_per_tok`` / ``serve_p99_ms`` of a Poisson
request stream through ``core/serving.ServingEngine`` —
benchmarks/serve_stream.py), a fused scan-train throughput row (``kind="train"``:
``train_ms_per_step`` / ``train_tokens_per_s`` of
``train_loop.build_train_chunk``, core/train_loop.py, same smoke scale,
with the pre-scan step-loop driver as the informational A/B column),
plus a host-speed calibration scalar so the CI gate
(``benchmarks/compare.py``, committed baseline
``benchmarks/BENCH_many_party.json``) can normalize across runner speeds.
``--gate`` is the exact preset the CI perf-gate job sweeps.

``--wire-modes float,int8`` reruns every per-C cell and the serve row
under each wire format: the int8 rows carry the narrow-ring compressed
``bytes_per_round`` (packed Z_2^8 uplink, ~4x fewer wire bytes), and the
gate preset sweeps both so compare.py can enforce that compression
keeps paying (int8 bytes strictly below float at every C).

Usage:
    PYTHONPATH=src python benchmarks/many_party_scaling.py          # full
    PYTHONPATH=src python benchmarks/many_party_scaling.py --smoke  # C=64
    PYTHONPATH=src python benchmarks/many_party_scaling.py \
        --gate --save experiments/bench/BENCH_many_party.json  # CI sweep
    PYTHONPATH=src python benchmarks/many_party_scaling.py \
        --mask-only --cs 128 --engine both --loop-max-c 128  # tentpole check
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import EasterConfig
from repro.core.party_models import PartyArch
from repro.core.protocol import EasterClassifier, split_features
from repro.launch.compile_cache import enable_compile_cache


def mlp_zoo(C: int, n_cls: int, d_embed: int) -> list:
    """Heterogeneous-but-groupable zoo: 4 distinct MLP shapes, cycled."""
    widths = [(64, 32), (32, 16), (96, 48), (48, 24)]
    return [PartyArch("mlp", widths[k % 4], (widths[k % 4][-1],), d_embed,
                      n_cls) for k in range(C)]


def build(C: int, n_feat_total: int, d_embed: int, n_cls: int,
          engine: str, use_kernel: bool, mask_mode: str,
          fused_masks: bool = False) -> tuple:
    x = jax.random.normal(jax.random.PRNGKey(0), (1, n_feat_total))
    nf = [v.shape[-1] for v in split_features(x, C)]
    arches = mlp_zoo(C, n_cls, d_embed)
    e = EasterConfig(num_passive=C - 1, d_embed=d_embed,
                     mask_mode=mask_mode)
    t0 = time.perf_counter()
    sys = EasterClassifier(e, arches, nf, engine=engine,
                           use_kernel=use_kernel, fused_masks=fused_masks)
    setup_s = time.perf_counter() - t0      # DH ceremony: K(K-1)/2 modexps
    return sys, nf, setup_s


def time_masks(sys, batch: int, rounds: int = 5) -> dict:
    """Per-round mask synthesis cost — the tentpole target (O(K^2) traced
    PRF ops in the loop oracle vs O(1) in the batched MaskEngine).

    With --fused-masks, synthesis is inseparable from aggregation by
    design (sys.masks() returns only a marker), so the columns report the
    fused blind+aggregate instead — comparable to mask synthesis + the
    jnp aggregate of the other rows, not to synthesis alone."""
    if sys.K < 2 or not sys.easter.enabled:
        return {"mask_first_ms": 0.0, "mask_ms": 0.0}
    if sys.fused_masks:
        from repro.core import aggregation
        E_all = jnp.zeros((sys.C, batch, sys.easter.d_embed), jnp.float32)
        f = jax.jit(lambda r: aggregation.blind_and_aggregate_fused(
            E_all, sys.mask_engine, r))
    else:
        f = jax.jit(lambda r: sys.masks(batch, r))
    t0 = time.perf_counter()
    m = f(jnp.asarray(0, jnp.int32))
    jax.block_until_ready(m)
    first = time.perf_counter() - t0
    # best-of-3 timed loops: the steady-state column feeds the CI perf
    # gate, so one scheduler spike must not fabricate a regression
    dt = float("inf")
    for rep in range(3):
        t0 = time.perf_counter()
        for r in range(1, rounds + 1):
            m = f(jnp.asarray(rep * rounds + r, jnp.int32))
        jax.block_until_ready(m)
        dt = min(dt, (time.perf_counter() - t0) / rounds)
    return {"mask_first_ms": first * 1e3, "mask_ms": dt * 1e3}


def time_rounds(sys, nf, batch: int, rounds: int, seed: int = 0) -> dict:
    key = jax.random.PRNGKey(seed)
    params = sys.init_params(key)
    init_opt, step = sys.make_train_step("adam", 1e-3)
    opt_state = init_opt(params)
    xs = [jax.random.normal(jax.random.fold_in(key, k), (batch, nf[k]))
          for k in range(sys.C)]
    y = jax.random.randint(jax.random.fold_in(key, 999), (batch,), 0,
                           sys.arches[0].n_classes)
    masks = sys.masks(batch, 0)
    t_trace = time.perf_counter()
    out = step(params, opt_state, xs, y, masks)       # compile + warmup
    jax.block_until_ready(out[2])
    trace_s = time.perf_counter() - t_trace
    # best-of-3 timed loops (see time_masks): gated metric, spike-robust
    dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(rounds):
            params, opt_state, total, per = step(params, opt_state, xs, y,
                                                 masks)
        jax.block_until_ready(total)
        dt = min(dt, (time.perf_counter() - t0) / rounds)
    return {"round_ms": dt * 1e3, "compile_s": trace_s,
            "rounds_per_s": 1.0 / dt, "loss": float(total),
            "n_groups": sys._eng.n_groups}


SCHEMA = "easter/many-party-bench/v2"

# the decode row's fixed shape: LLM smoke scale, C=4 (the paper's party
# count). MUST stay in sync with the committed baseline's config block.
DECODE_BATCH, DECODE_PROMPT, DECODE_ARCH = 2, 8, "qwen2.5-3b"
# the kind="train" row's fixed shape (same LLM smoke system)
TRAIN_BATCH, TRAIN_SEQ = 2, 8


def time_decode(gen: int, engine: str = "vectorized", reps: int = 3) -> dict:
    """Fused scan-decode throughput: the lane-batched decode engine
    behind ``core/api.build_decoder`` (ONE compiled early-exit loop over
    ``gen`` EASTER serve rounds, blinded uplink per step with per-lane
    PRF nonces — core/decode.py) at LLM smoke scale.

    ``decode_ms_per_tok`` (min-of-reps steady state) is the gated
    metric; ``tokens_per_s`` is the dashboard-friendly inverse
    (batch-scaled). Every lane carries a full-budget request with EOS
    disabled, so the loop runs exactly ``gen`` rounds — the raw engine
    number the serve tier's end-to-end row (kind="serve") builds on.
    The timing loop replays one prefilled ``DecodeState``, so the
    decoder runs with ``donate=False`` (donation would consume the
    state on the first call; the dispatch count — one per generation —
    is identical either way)."""
    from repro.configs.base import get_config, smoke_variant
    from repro.core import api
    from repro.core.easter_lm import EasterLM

    cfg = smoke_variant(get_config(DECODE_ARCH))
    e = EasterConfig(num_passive=3, d_embed=64, decision_layers=1)
    lm = EasterLM(cfg=cfg, easter=e, engine=engine)
    params = lm.init_params(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1),
                              (DECODE_BATCH, DECODE_PROMPT), 0,
                              cfg.vocab_size)
    dcfg = api.DecodeConfig(lanes=DECODE_BATCH,
                            max_len=DECODE_PROMPT + gen, chunk=gen,
                            donate=False)
    prefill_fn, decode_fn = api.build_decoder(lm, dcfg)
    state = api.init_decode_state(lm, dcfg)
    for lane in range(DECODE_BATCH):
        req = api.ServeRequest(
            tokens=tuple(int(t) for t in toks[lane].tolist()),
            max_new_tokens=gen, eos_id=-1, temperature=0.0)
        state = prefill_fn(params, state, req, lane, nonce=lane)
    jax.block_until_ready(state.pos)
    t0 = time.perf_counter()
    out = decode_fn(params, state)
    jax.block_until_ready(out[0])
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = decode_fn(params, state)
        jax.block_until_ready(out[0])
        best = min(best, time.perf_counter() - t0)
    row = {"kind": "decode", "C": 4, "engine": engine,
           "batch": DECODE_BATCH, "gen": gen,
           "decode_ms_per_tok": best * 1e3 / gen,
           "tokens_per_s": DECODE_BATCH * gen / best,
           "compile_s": compile_s,
           "cal_ms": calibration_ms(20)}
    _annotate_sharded_lm(row, lm, "decode")
    return row


def time_train(chunk: int, engine: str = "vectorized", reps: int = 3
               ) -> dict:
    """Fused scan-train throughput: ``core/train_loop.build_train_chunk``
    (ONE compiled ``lax.scan`` over ``chunk`` EASTER optimizer steps —
    blinded round + grads + update per step) at LLM smoke scale, vs the
    step-at-a-time jitted loop it replaced.

    ``train_ms_per_step`` (min-of-reps steady state of the fused chunk)
    is the gated metric; ``train_tokens_per_s`` is the dashboard-friendly
    inverse (batch x seq scaled). ``step_loop_ms_per_step`` is the
    informational pre-scan driver column (one jit dispatch per optimizer
    step — the dispatch-overhead A/B). The timing loop replays one
    training state, so the builder runs with ``donate=False`` (donation
    would consume params/opt state on the first call; the dispatch
    count — one per chunk — is identical either way)."""
    from repro.configs.base import get_config, smoke_variant
    from repro.core import train_loop
    from repro.core.easter_lm import EasterLM
    from repro.optim import make_optimizer

    cfg = smoke_variant(get_config(DECODE_ARCH))
    e = EasterConfig(num_passive=3, d_embed=64, decision_layers=1)
    lm = EasterLM(cfg=cfg, easter=e, engine=engine)
    params = lm.init_params(jax.random.PRNGKey(0))
    opt = make_optimizer("adam", 1e-3)
    opt_state = opt.init(params)
    toks = jax.random.randint(jax.random.PRNGKey(1),
                              (chunk, TRAIN_BATCH, TRAIN_SEQ + 1), 0,
                              cfg.vocab_size)
    batches = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
    step0 = jnp.asarray(0, jnp.int32)
    fn = train_loop.build_train_chunk(lm, opt, donate=False)
    t0 = time.perf_counter()
    out = fn(params, opt_state, batches, step0)
    jax.block_until_ready(out[3]["loss"])
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(params, opt_state, batches, step0)
        jax.block_until_ready(out[3]["loss"])
        best = min(best, time.perf_counter() - t0)
    # the pre-scan driver: one jitted train-step dispatch per step, state
    # rebound between dispatches exactly like launch/train.py --chunk 1
    # (the data dependency matters — independent dispatches would overlap
    # under async dispatch and under-measure the driver)
    step_fn = jax.jit(train_loop.make_train_step(lm, opt))
    bs = [jax.tree.map(lambda x, i=i: x[i], batches) for i in range(chunk)]
    o = step_fn(params, opt_state, bs[0], step0)
    jax.block_until_ready(o[2]["loss"])
    best_sl = float("inf")
    for _ in range(reps):
        p, s = params, opt_state
        t0 = time.perf_counter()
        for i in range(chunk):
            p, s, m = step_fn(p, s, bs[i], jnp.asarray(i, jnp.int32))
        jax.block_until_ready(m["loss"])
        best_sl = min(best_sl, time.perf_counter() - t0)
    row = {"kind": "train", "C": 4, "engine": engine,
           "batch": TRAIN_BATCH, "seq": TRAIN_SEQ, "chunk": chunk,
           "train_ms_per_step": best * 1e3 / chunk,
           "train_tokens_per_s": TRAIN_BATCH * TRAIN_SEQ * chunk / best,
           "step_loop_ms_per_step": best_sl * 1e3 / chunk,
           "compile_s": compile_s,
           "cal_ms": calibration_ms(20)}
    _annotate_sharded_lm(row, lm, "train")
    return row


def _annotate_sharded_lm(row: dict, lm, kind: str) -> None:
    """For LLM-scale rows swept with engine="sharded": record what
    actually ran (cf. the paper-scale sweep rows) — K=3 passives on a
    non-dividing or 1-device axis degrade to plain vmap; don't pass
    vectorized numbers off as a sharded measurement."""
    if row["engine"] != "sharded":
        return
    from repro import sharding as shard_rules
    ok = lm._shard_ok()
    row["party_devices"] = (shard_rules.party_axis_size(lm.party_mesh)
                            if ok else 1)
    if not ok:
        print(f"many_party {kind} engine=sharded WARNING: passive group "
              f"does not divide the party axis — row measures the "
              f"vectorized fallback")


def calibration_ms(reps: int = 50) -> float:
    """Host-speed probe: MIN ms of a jitted 1024x1024 fp32 matmul.

    Stored alongside the timing rows so ``compare.py`` can normalize a
    run on a fast dev box against a baseline captured on a slow CI
    runner (and vice versa) before applying the regression threshold.
    Min over many single-shot reps — the fastest observation estimates
    hardware capability and is by far the most stable statistic under
    scheduler noise; a mean/median would inject its own jitter into
    EVERY normalized ratio the gate checks.
    """
    x = jnp.ones((1024, 1024), jnp.float32)
    f = jax.jit(lambda a: a @ a)
    for _ in range(5):
        jax.block_until_ready(f(x))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


_MIN_MERGE = ("setup_s", "mask_first_ms", "mask_ms", "round_ms",
              "compile_s", "cal_ms", "decode_ms_per_tok",
              "train_ms_per_step", "step_loop_ms_per_step",
              "serve_ms_per_tok", "serve_p50_ms", "serve_p99_ms")


def _merge_min(prev: dict, new: dict) -> dict:
    """Per-metric min across repeated sweeps of the same cell: shared
    hosts drift between speed regimes for minutes at a time, so two
    samples of a cell taken a sweep apart beat any within-cell
    statistic. The fastest observation is the capability estimate."""
    out = dict(prev)
    for k in _MIN_MERGE:
        if k in prev and k in new:
            out[k] = min(prev[k], new[k])
    if "round_ms" in out and out["round_ms"] > 0:
        out["rounds_per_s"] = 1e3 / out["round_ms"]
    if "decode_ms_per_tok" in out and out["decode_ms_per_tok"] > 0:
        out["tokens_per_s"] = out["batch"] * 1e3 / out["decode_ms_per_tok"]
    if "train_ms_per_step" in out and out["train_ms_per_step"] > 0:
        out["train_tokens_per_s"] = (out["batch"] * out["seq"] * 1e3
                                     / out["train_ms_per_step"])
    if "serve_ms_per_tok" in out and out["serve_ms_per_tok"] > 0:
        out["agg_tokens_per_s"] = 1e3 / out["serve_ms_per_tok"]
    return out


def _serve_stream_mod():
    """Load benchmarks/serve_stream.py next to this file (the benchmarks
    dir is not a package; loading by path keeps both scripts runnable
    from any cwd)."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "serve_stream.py")
    spec = importlib.util.spec_from_file_location("serve_stream", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run(cs, engines, batch, rounds, d_embed, n_feat_total, use_kernel,
        mask_mode, loop_max_c, fused_masks=False, mask_only=False,
        save=None, repeat=1, decode_gen=0, train_chunk=0,
        serve_requests=0, serve_lanes=8, wire_modes=None):
    # wire sweep: every per-C cell and the serve row run once per wire
    # format, so narrow-ring compression (mask_mode="int8") shows up as
    # its own dashboard rows — bytes_per_round is what the gate checks.
    wire_modes = list(wire_modes) if wire_modes else [mask_mode]
    merged = {}
    ss = _serve_stream_mod() if serve_requests and not mask_only else None
    for rep in range(repeat):
        if ss is not None:
            # continuous-batching serve tier end-to-end (Poisson request
            # stream through core/serving.ServingEngine; see
            # serve_stream.time_serve). Engine pinned like the decode row.
            sv_eng = engines[0] if len(set(engines)) == 1 else "vectorized"
            for wire in wire_modes:
                r = ss.time_serve(serve_lanes, serve_requests,
                                  engine=sv_eng, wire=wire)
                k_sv = ("serve", r["engine"], r.get("wire", "float"))
                merged[k_sv] = (r if k_sv not in merged
                                else _merge_min(merged[k_sv], r))
                rm = merged[k_sv]
                print(f"many_party serve  engine={r['engine']:10s} "
                      f"wire={wire:6s} "
                      f"req {serve_requests:2d} x{serve_lanes} lanes  "
                      f"{rm['serve_ms_per_tok']:8.2f} ms/tok aggregate  "
                      f"(p50 {rm['serve_p50_ms']:6.1f} ms, "
                      f"p99 {rm['serve_p99_ms']:6.1f} ms)  "
                      f"compile {r['compile_s']:6.1f} s"
                      + (f"  [pass {rep + 1}/{repeat}]"
                         if repeat > 1 else ""))
        if train_chunk and not mask_only:
            # fused scan-train throughput (see time_train). Swept once
            # per pass like every other cell so the min-merge defeats
            # host speed-regime drift; engine pinned like the decode row.
            tr_eng = engines[0] if len(set(engines)) == 1 else "vectorized"
            r = time_train(train_chunk, engine=tr_eng)
            k_tr = ("train", r["engine"])
            merged[k_tr] = (r if k_tr not in merged
                            else _merge_min(merged[k_tr], r))
            rm = merged[k_tr]
            print(f"many_party train  engine={r['engine']:10s} "
                  f"chunk {train_chunk:2d} x{r['batch']}x{r['seq']}  "
                  f"{rm['train_ms_per_step']:8.2f} ms/step fused  "
                  f"({rm['step_loop_ms_per_step']:8.2f} step-loop, "
                  f"{rm['train_tokens_per_s']:6.1f} tok/s)  "
                  f"compile {r['compile_s']:6.1f} s"
                  + (f"  [pass {rep + 1}/{repeat}]" if repeat > 1 else ""))
        if decode_gen and not mask_only:
            # fused scan-decode throughput (serve path; see time_decode).
            # Swept once per pass like every other cell so the min-merge
            # defeats host speed-regime drift. The row follows the
            # sweep's engine when unambiguous; mixed sweeps (and the CI
            # gate) pin the vectorized engine.
            dec_eng = engines[0] if len(set(engines)) == 1 else "vectorized"
            r = time_decode(decode_gen, engine=dec_eng)
            k_dec = ("decode", r["engine"])
            merged[k_dec] = (r if k_dec not in merged
                             else _merge_min(merged[k_dec], r))
            rm = merged[k_dec]
            print(f"many_party decode engine={r['engine']:10s} "
                  f"gen {decode_gen:3d} x{r['batch']}  "
                  f"{rm['decode_ms_per_tok']:8.2f} ms/tok  "
                  f"({rm['tokens_per_s']:6.1f} tok/s)  "
                  f"compile {r['compile_s']:6.1f} s"
                  + (f"  [pass {rep + 1}/{repeat}]" if repeat > 1 else ""))
        for C in cs:
            for eng in engines:
                if eng == "loop" and C > loop_max_c:
                    print(f"many_party C={C} engine=loop skipped "
                          f"(> --loop-max-c {loop_max_c})")
                    continue

                for wire in wire_modes:
                    # in-kernel mask synthesis only exists for the float
                    # wire; ring modes take the MaskEngine path
                    fused_eff = (fused_masks and eng == "vectorized"
                                 and wire == "float")
                    sys, nf, setup_s = build(C, n_feat_total, d_embed, 10,
                                             eng, use_kernel, wire,
                                             fused_eff)
                    r = {"C": C, "engine": eng, "batch": batch,
                         "use_kernel": use_kernel, "fused_masks": fused_eff,
                         "wire": wire, "setup_s": setup_s,
                         "bytes_per_round": sys.bytes_per_round(batch)}
                    if eng == "sharded":
                        # record what actually ran: on a 1-device host (or
                        # when no group divides the axis) the sharded
                        # engine degrades to plain vmap — don't let a
                        # dashboard row labeled "sharded" pass off
                        # vectorized numbers
                        from repro import sharding as shard_rules
                        pdev = shard_rules.party_axis_size(sys.mesh)
                        sharded_eff = any(
                            shard_rules.party_shardable(sys.mesh, len(idx))
                            for _, idx in sys._eng.groups)
                        r["party_devices"] = pdev if sharded_eff else 1
                        if not sharded_eff:
                            print(f"many_party C={C} engine=sharded "
                                  f"WARNING: no party group divides the "
                                  f"{pdev}-way axis — rows measure the "
                                  f"vectorized fallback")
                    # rep counts scale inversely with C: the small-C cells
                    # are sub-millisecond and feed the CI gate, so they
                    # need many more reps than C=128 to beat scheduler
                    # noise
                    r.update(time_masks(sys, batch,
                                        rounds=max(5, 512 // C)))
                    if not mask_only:
                        r.update(time_rounds(sys, nf, batch,
                                             max(rounds, 256 // C)))
                    # per-row host-speed probe: the gate normalizes each
                    # cell by a calibration measured right next to it
                    r["cal_ms"] = calibration_ms(20)
                    key = (C, eng, use_kernel, fused_eff, wire)
                    merged[key] = (r if key not in merged
                                   else _merge_min(merged[key], r))
                    round_txt = ("" if mask_only else
                                 f"round {r['round_ms']:8.2f} ms  "
                                 f"compile {r['compile_s']:6.1f} s  "
                                 f"loss {r['loss']:.3f}  ")
                    print(f"many_party C={C:4d} engine={eng:10s} "
                          f"wire={wire:6s} "
                          f"{round_txt}"
                          f"ceremony {setup_s:5.1f} s  "
                          f"mask_first {r['mask_first_ms']:9.1f} ms  "
                          f"mask {r['mask_ms']:7.2f} ms  "
                          f"bytes/round {r['bytes_per_round']:9d}"
                          + (f"  [pass {rep + 1}/{repeat}]"
                             if repeat > 1 else ""))
    rows = list(merged.values())
    if save:
        payload = {
            "schema": SCHEMA,
            "generated_by": "benchmarks/many_party_scaling.py",
            "jax_version": jax.__version__,
            "device_count": jax.device_count(),
            "calibration_ms": calibration_ms(),
            "config": {"batch": batch, "rounds": rounds, "d_embed": d_embed,
                       "n_features": n_feat_total, "mask_mode": mask_mode,
                       "wire_modes": wire_modes,
                       "mask_only": mask_only,
                       "decode": {"gen": decode_gen, "batch": DECODE_BATCH,
                                  "prompt": DECODE_PROMPT,
                                  "arch": DECODE_ARCH},
                       "train": {"chunk": train_chunk,
                                 "batch": TRAIN_BATCH, "seq": TRAIN_SEQ,
                                 "arch": DECODE_ARCH},
                       "serve": {"requests": serve_requests,
                                 "lanes": serve_lanes,
                                 "prompt": (ss.SERVE_PROMPT if ss else 0),
                                 "gen": (ss.SERVE_GEN if ss else 0),
                                 "chunk": (ss.SERVE_CHUNK if ss else 0),
                                 "arch": DECODE_ARCH}},
            "rows": rows,
        }
        os.makedirs(os.path.dirname(save) or ".", exist_ok=True)
        with open(save, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"saved -> {save}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cs", default="4,16,64,128",
                    help="comma-separated party counts")
    ap.add_argument("--smoke", action="store_true",
                    help="C=64 only, reduced shapes (CI-runnable)")
    ap.add_argument("--gate", action="store_true",
                    help="the CI perf-gate preset: C in {4,16,64}, "
                         "vectorized engine, reduced shapes — the sweep "
                         "benchmarks/compare.py gates against the "
                         "committed benchmarks/BENCH_many_party.json")
    ap.add_argument("--engine", default="both",
                    choices=["both", "vectorized", "sharded", "loop"])
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--d-embed", type=int, default=64)
    ap.add_argument("--n-features", type=int, default=1024)
    ap.add_argument("--use-kernel", action="store_true",
                    help="fused Pallas blind_agg (interpret mode off-TPU)")
    ap.add_argument("--fused-masks", action="store_true",
                    help="in-kernel pltpu-PRNG mask synthesis (vectorized "
                         "engine only; MaskEngine fallback off-TPU)")
    ap.add_argument("--mask-mode", default="float",
                    choices=["float", "int32", "int8"])
    ap.add_argument("--wire-modes", default="",
                    help="comma-separated wire formats to sweep per cell "
                         "(e.g. float,int8); empty = just --mask-mode. "
                         "The gate preset sweeps float,int8 so narrow-"
                         "ring compression is gated as its own rows")
    ap.add_argument("--mask-only", action="store_true",
                    help="time mask synthesis only (skip training rounds)")
    ap.add_argument("--loop-max-c", type=int, default=16,
                    help="skip the loop engine above this C")
    ap.add_argument("--decode-gen", type=int, default=16,
                    help="tokens per fused scan-decode throughput row "
                         "(0 = skip the decode row)")
    ap.add_argument("--train-chunk", type=int, default=4,
                    help="optimizer steps per fused scan-train "
                         "throughput row (kind=\"train\"; 0 = skip)")
    ap.add_argument("--serve-requests", type=int, default=16,
                    help="requests in the continuous-batching serve-tier "
                         "row (kind=\"serve\", benchmarks/serve_stream.py; "
                         "0 = skip)")
    ap.add_argument("--serve-lanes", type=int, default=8,
                    help="decode lanes for the kind=\"serve\" row")
    ap.add_argument("--repeat", type=int, default=1,
                    help="sweep every cell this many times (min-merged) — "
                         "defeats minute-scale host speed-regime drift")
    ap.add_argument("--save", default="experiments/bench/many_party.json")
    a = ap.parse_args()
    wire_modes = ([w for w in a.wire_modes.split(",") if w]
                  if a.wire_modes else None)
    if a.gate:
        # MUST stay in sync with the committed baseline's config block —
        # compare.py refuses to gate across mismatched configs
        cs, engines = [4, 16, 64], ["vectorized"]
        a.batch, a.rounds, a.n_features, a.d_embed = 32, 5, 256, 64
        a.decode_gen = 16
        a.train_chunk = 4
        a.serve_requests, a.serve_lanes = 16, 8
        a.repeat = max(a.repeat, 2)
        wire_modes = ["float", "int8"]
        save = a.save
    elif a.smoke:
        cs, engines = [64], ["vectorized"]
        a.batch, a.rounds, a.n_features = 32, 5, 256
        a.decode_gen = 0
        a.train_chunk = 0
        a.serve_requests = 0
        save = None
    else:
        cs = [int(c) for c in a.cs.split(",")]
        engines = (["vectorized", "loop"] if a.engine == "both"
                   else [a.engine])
        save = a.save
    run(cs, engines, a.batch, a.rounds, a.d_embed, a.n_features,
        a.use_kernel, a.mask_mode, a.loop_max_c,
        fused_masks=a.fused_masks, mask_only=a.mask_only, save=save,
        repeat=a.repeat, decode_gen=a.decode_gen,
        train_chunk=a.train_chunk, serve_requests=a.serve_requests,
        serve_lanes=a.serve_lanes, wire_modes=wire_modes)


if __name__ == "__main__":
    enable_compile_cache()
    main()
