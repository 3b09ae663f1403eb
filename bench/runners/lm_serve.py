"""Closed loop of clients over the EASTER language-model serving engine.

``clients`` clients each send a request, wait for its last token and send
their next one at once, so the queue never grows. The engine
(``serving.ServingEngine`` over ``api.build_decoder``) admits requests
into free lanes (one prefill each), advances every live lane a token per
protocol round in chunks of ``chunk`` rounds, and harvests after each
chunk; this runner stamps a request's first token at the first harvest
that holds one.

The pool of request sizes is drawn once from the traffic file's
``size_seed``; ``--seed`` orders it and draws the token ids. Clients take
requests from the pool in turn, cycling through it. The pool holds about
as many requests as one window sends, so that every seed's window serves
nearly the same sizes, only in another order. Set-up warms
one prefill program per prompt bucket and the decode program, then runs
``warmup_seconds`` of traffic so that the window starts with every lane
busy. After the window no new
request is sent; the requests sent in it run to their end (at most
``drain_seconds`` more), and one that does not is failed.

After that, a sample of the finished requests drawn from the seed, the
longest among them, goes through the plain reference
(``bench/reference/lm.py``): at each position where the engine served a
token, the number compared is how far that token's reference logit lies
below the reference's best.

Traffic keys: ``lanes``, ``max_len``, ``chunk``, ``clients``, ``prompt``
and ``output`` (lognormal ``median`` and ``sigma``, clipped to ``min`` and
``max``; prompts rounded up to ``buckets``), ``size_seed``, ``pool``,
``warmup_seconds``, ``drain_seconds``, ``check_tokens``.

The engine records no first-token time, so this runner reads the tokens
each live lane holds after every step from ``ServingEngine._lanes``: a
private field, until the program's completions carry that time.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional


@dataclass
class Sent:
    request: object
    t_send: float
    in_window: bool
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    tokens: Optional[List[int]] = None


def request_sizes(tr) -> list:
    """(prompt length, output budget) of every request in the pool; the
    same for every seed."""
    import numpy as np
    from bench import gen
    rng = np.random.default_rng(tr["size_seed"])
    p, o = tr["prompt"], tr["output"]
    n = tr["pool"]
    plen = gen.bucket_up(gen.lognormal_ints(rng, n, median=p["median"],
                                            sigma=p["sigma"], lo=p["min"],
                                            hi=p["max"]), p["buckets"])
    out = gen.lognormal_ints(rng, n, median=o["median"], sigma=o["sigma"],
                             lo=o["min"], hi=o["max"])
    return list(zip(plen.tolist(), out.tolist()))


def requests(ctx) -> list:
    """The pool of requests in this seed's order, greedy, EOS on."""
    from repro.core import api
    from bench import gen
    sizes = request_sizes(ctx.traffic)
    rng = ctx.rng(4)
    order = rng.permutation(len(sizes))
    eos = ctx.config["eos_token_id"]
    out = []
    for i in order:
        P, budget = sizes[i]
        ids = gen.uniform_tokens(rng, (P,), ctx.config["vocab_size"],
                                 exclude=(eos,))
        out.append(api.ServeRequest(tokens=tuple(ids.tolist()),
                                    max_new_tokens=int(budget), eos_id=eos))
    return out


def reference_gaps(ctx, shapes, done: List[Sent], *, lowp=None) -> float:
    """Widest gap, over the served tokens of ``done``, between the
    reference's best logit and the logit of the token served (``lowp``:
    of the token that the reference in that precision puts first)."""
    import jax
    import jax.numpy as jnp
    from bench import lm_system
    from bench.reference import lm as ref
    parties = lm_system.initial_parties(ctx, shapes, served=True)
    m = ctx.config
    C = len(parties)
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        emb = jax.jit(lambda p, t, lp=None: ref.embed(p, t, m, lp),
                      static_argnums=2)
        lg = jax.jit(lambda p, E, lp=None: ref.logits(p, E, m, lp),
                     static_argnums=2)
        for s in done:
            # every sequence padded to the lane length: one program for all
            # (attention is causal, so the padding changes no position read)
            prompt = list(s.request.tokens)
            served = s.tokens
            seq = prompt + served[:-1]
            n = len(seq)
            seq = jnp.asarray([seq + [0] * (ctx.traffic["max_len"] - n)],
                              jnp.int32)
            E = sum(emb(p, seq, None) for p in parties) / C
            full = lg(parties[0], E, None)[0, len(prompt) - 1:n]
            best = jnp.max(full, -1)
            if lowp is None:
                pick = jnp.asarray(served, jnp.int32)
            else:
                El = sum(emb(p, seq, lowp) for p in parties) / C
                pick = jnp.argmax(lg(parties[0], El, lowp)[0,
                                  len(prompt) - 1:n], -1)
            got = jnp.take_along_axis(full, pick[:, None], -1)[:, 0]
            worst = max(worst, float(jnp.max(best - got)))
    return worst


def check_sample(ctx, done: List[Sent]) -> List[Sent]:
    """Finished requests drawn from the seed, the longest first, until
    they hold ``check_tokens`` served tokens."""
    rng = ctx.rng(5)
    pool = sorted(done, key=lambda s: -len(s.tokens))
    picked, rest = [pool[0]], pool[1:]
    order = rng.permutation(len(rest))
    for i in order:
        if sum(len(s.tokens) for s in picked) >= ctx.traffic["check_tokens"]:
            break
        picked.append(rest[i])
    return picked


def control(ctx, mode: str) -> dict:
    """``control``: the reference with every matrix product's operands in
    float8 (the next precision below the configuration's bfloat16) picks
    the tokens, at the positions of a run's sample; the run's own reading
    comes beside it."""
    if mode != "control":
        raise ValueError(f"no control mode {mode!r}")
    out, sample, shapes = serve(ctx)
    sound = out.verify()[0].value
    return {"logit_gap": reference_gaps(ctx, shapes, sample,
                                        lowp="float8_e4m3fn"),
            "sound_logit_gap": sound}


def percentile(values, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


def run(ctx):
    return serve(ctx)[0]


def serve(ctx):
    """The run: its Outcome, the sample its check compares, the shapes."""
    import gc
    import jax
    import numpy as np
    from repro.core import serving
    from bench import flops, lm_system
    from bench.harness import Check, Outcome

    tr, m = ctx.traffic, ctx.config
    system, shapes = lm_system.build(ctx)
    params = {"parties": lm_system.initial_parties(ctx, shapes, served=True)}
    # greedy decoding draws no sample; the sampling key stays fixed, since
    # the prefill programs compile it in and a seed's own key made every
    # seed compile its own four (12 s of set-up each on a TPU v5 lite)
    eng = serving.ServingEngine(system, params, lanes=tr["lanes"],
                                max_len=tr["max_len"], chunk=tr["chunk"],
                                base_key=0)
    pool = requests(ctx)
    ctx.log("federation built, weights made")

    # every prompt bucket's prefill program and the decode program
    from repro.core import api
    for P in tr["prompt"]["buckets"]:
        eng.submit(api.ServeRequest(tokens=tuple(range(1, P + 1)),
                                    max_new_tokens=tr["chunk"],
                                    eos_id=-1))
    eng.run()
    ctx.log("prefill and decode programs warm")

    sent, live = [], {}          # every request sent; those in flight
    stats = {"tokens": 0, "decode_flops": 0.0, "kv_reads": 0.0}
    state = {"next": 0, "sending": True, "in_window": False,
             "counting": False}
    seen = [len(eng.completions)]
    a_tok = flops.lm_decode_token(m, 0.0)
    b_tok = flops.lm_decode_token(m, 1.0) - a_tok

    def send():
        req = pool[state["next"] % len(pool)]
        state["next"] += 1
        eng.submit(req)
        s = Sent(req, time.perf_counter(), state["in_window"])
        sent.append(s)
        live[id(req)] = s

    def step():
        before = {lane: (id(st.request), len(st.tokens))
                  for lane, st in enumerate(eng._lanes) if st is not None}
        with ctx.span("step"):
            eng.step()
        t = time.perf_counter()
        new = 0
        for lane, st in enumerate(eng._lanes):
            if st is not None:
                s = live[id(st.request)]
                if st.tokens and s.t_first is None:
                    s.t_first = t
                k0 = before.get(lane, (None, 0))
                n0 = k0[1] if k0[0] == id(st.request) else 0
                new += _count(s, n0, len(st.tokens))
        for c in eng.completions[seen[0]:]:
            s = live.pop(id(c.request))
            k0 = [b for b in before.values() if b[0] == id(c.request)]
            n0 = k0[0][1] if k0 else 0
            new += _count(s, n0, len(c.tokens))
            if s.t_first is None:
                s.t_first = t
            s.t_done, s.tokens = t, list(c.tokens)
            if state["sending"]:
                send()
        seen[0] = len(eng.completions)
        if state["counting"]:
            stats["tokens"] += new

    def _count(s, n0, n1):
        """Tokens n0..n1 of request s were served at this harvest."""
        if state["counting"] and n1 > n0:
            P = len(s.request.tokens)
            ctxs = np.arange(P + n0, P + n1, dtype=np.float64)
            stats["decode_flops"] += (n1 - n0) * a_tok + b_tok * ctxs.sum()
            stats["kv_reads"] += ctxs.sum()
        return n1 - n0

    for _ in range(tr["clients"]):
        send()
    t_warm = time.perf_counter() + tr["warmup_seconds"]
    while time.perf_counter() < t_warm:
        step()
    ctx.log(f"warm-up traffic: {len(eng.completions)} requests done")

    rounds0 = eng.rounds_run
    state["in_window"] = state["counting"] = True
    with ctx.window():
        t_end = time.perf_counter() + ctx.window_seconds
        while time.perf_counter() < t_end:
            step()
    state["in_window"] = state["counting"] = state["sending"] = False
    rounds = eng.rounds_run - rounds0
    t_drain = time.perf_counter() + tr["drain_seconds"]
    window = [s for s in sent if s.in_window]
    while any(s.t_done is None for s in window) and \
            time.perf_counter() < t_drain:
        step()
    done = [s for s in window if s.t_done is not None]
    failed = len(window) - len(done)
    ttft = [1e3 * (s.t_first - s.t_send) for s in done]
    tpot = [1e3 * (s.t_done - s.t_first) / (len(s.tokens) - 1)
            for s in done if len(s.tokens) > 1]
    ctx.log(f"window: {len(window)} requests sent, {stats['tokens']} "
            f"tokens, {rounds} decode rounds; drained, {failed} unfinished")

    sample = check_sample(ctx, done) if done else []

    def free():
        nonlocal eng, params
        eng = params = None
        gc.collect()

    def verify():
        free()
        if not sample:
            return [Check("logit_gap", float("inf"), ctx.limits["logit_gap"])]
        gap = reference_gaps(ctx, shapes, sample)
        ctx.log(f"reference over {len(sample)} requests, "
                f"{sum(len(s.tokens) for s in sample)} served tokens")
        return [Check("logit_gap", gap, ctx.limits["logit_gap"])]

    return Outcome(
        attempted=len(window), failed=failed,
        metrics={"serve_tokens_per_s": stats["tokens"] / ctx.window_s,
                 "ttft_p95_ms": percentile(ttft, 95),
                 "tpot_p95_ms": percentile(tpot, 95)},
        counters={"decode_rounds": rounds,
                  "prefill_flops": sum(flops.lm_prefill(
                      m, len(s.request.tokens) - 1) for s in window),
                  "decode_flops": stats["decode_flops"],
                  "decode_bytes": rounds * flops.lm_decode_weight_bytes(m)
                  + stats["tokens"] * flops.lm_embed_row_bytes(m)
                  + stats["kv_reads"] * flops.lm_kv_bytes_per_token(m),
                  "decode_program": "jit_run",
                  "prefill_program": "jit__prefill_into"},
        verify=verify), sample, shapes
