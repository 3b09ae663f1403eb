"""Plain reference of the EASTER language-model federation.

Written from the published descriptions, importing nothing of the program.

The backbone is Qwen2 (arXiv:2407.10671): token embedding; per layer an
RMSNorm, grouped-query attention with biased q/k/v projections, rotary
position embedding (rotate-half form, base ``rope_theta``) and causal
softmax at ``1/sqrt(head_dim)``, an output projection and a residual; then
an RMSNorm, a SwiGLU MLP ``down(silu(gate x) * up x)`` and a residual; a
final RMSNorm. Each EASTER party (arXiv:2310.13367) projects its backbone's
hidden states into the shared embedding space (``proj``), the active party
averages the parties' embeddings (the pairwise masks cancel in that mean,
so the reference leaves them out), and each party's decision net, a stack
of pre-norm SwiGLU residual blocks of width ``4 d_embed``, a final RMSNorm
and its LM head, turns the aggregate into next-token logits.

Parameters are the benchmark's own (``bench.weights``), laid out per party
as ``{"backbone": {"embed", "segments": [{"p0": layers stacked}],
"final_norm"}, "proj", "decision", "final_norm", "head"}``. Everything
computes in float32 at full matmul precision, a layer at a time, unless
``lowp`` names a narrower type: then every matrix product's operands are
rounded to it first (the control).
"""
from __future__ import annotations

import math
from typing import Optional

F32 = "float32"


def _mm(a, b, lowp):
    import jax.numpy as jnp
    if lowp is not None:
        a = a.astype(lowp).astype(jnp.float32)
        b = b.astype(lowp).astype(jnp.float32)
    return a @ b


def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (B, S, H, hd), pos (B, S)."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * inv         # (B, S, half)
    c, s = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _swiglu(p, x, lowp):
    import jax
    f = lambda n: _mm(x, p[n]["w"].astype("float32"), lowp)  # noqa: E731
    return _mm(jax.nn.silu(f("gate")) * f("up"),
               p["down"]["w"].astype("float32"), lowp)


def layer(p, x, pos, m: dict, lowp=None):
    """One decoder layer over a whole sequence. ``p``: one layer's leaves;
    ``x`` (B, S, d); ``pos`` (B, S) positions."""
    import jax
    import jax.numpy as jnp
    B, S, _ = x.shape
    H, Hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    eps = m["rms_norm_eps"]
    a = p["attn"]
    h = _rms(x, p["ln1"]["scale"], eps)

    def proj(n, heads):
        y = _mm(h, a[n]["w"].astype(F32), lowp)
        if "b" in a[n]:
            y = y + a[n]["b"].astype(F32)
        return y.reshape(B, S, heads, hd)

    q = _rope(proj("wq", H), pos, m["rope_theta"])
    k = _rope(proj("wk", Hkv), pos, m["rope_theta"])
    v = proj("wv", Hkv)
    G = H // Hkv
    qg = q.reshape(B, S, Hkv, G, hd) / math.sqrt(hd)
    if lowp is not None:
        qg = qg.astype(lowp).astype(F32)
        kk, vv = k.astype(lowp).astype(F32), v.astype(lowp).astype(F32)
    else:
        kk, vv = k, v
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, kk)
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, -1)
    if lowp is not None:
        probs = probs.astype(lowp).astype(F32)
    o = jnp.einsum("bkgst,btkd->bskgd", probs, vv).reshape(B, S, H * hd)
    x = x + _mm(o, a["wo"]["w"].astype(F32), lowp)
    return x + _swiglu(p["mlp"], _rms(x, p["ln2"]["scale"], eps), lowp)


def n_layers(party) -> int:
    import jax
    seg = party["backbone"]["segments"][0]["p0"]
    return jax.tree.leaves(seg)[0].shape[0]


def layer_params(party, i: int):
    import jax
    return jax.tree.map(lambda a: a[i],
                        party["backbone"]["segments"][0]["p0"])


def embed(party, tokens, m: dict, lowp=None):
    """A party's embedding into the shared space: (B, S, d_embed)."""
    import jax.numpy as jnp
    B, S = tokens.shape
    x = party["backbone"]["embed"]["table"].astype(F32)[tokens]
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    for i in range(n_layers(party)):
        x = layer(layer_params(party, i), x, pos, m, lowp)
    x = _rms(x, party["backbone"]["final_norm"]["scale"], m["rms_norm_eps"])
    return _mm(x, party["proj"]["w"].astype(F32), lowp)


def decide_hidden(party, E, m: dict, lowp=None):
    x = E
    for blk in party["decision"]:
        x = x + _swiglu(blk["mlp"], _rms(x, blk["ln"]["scale"],
                                         m["rms_norm_eps"]), lowp)
    return _rms(x, party["final_norm"]["scale"], m["rms_norm_eps"])


def logits(party, E, m: dict, lowp=None):
    return _mm(decide_hidden(party, E, m, lowp),
               party["head"]["w"].astype(F32), lowp)


def xent(lg, labels):
    import jax
    import jax.numpy as jnp
    logz = jax.nn.logsumexp(lg, -1)
    ll = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - ll)


def party_loss(party, tokens, labels, E_others, C: int, m: dict,
               lowp=None):
    """Party k's own loss: its decision over the aggregate, whose other
    parties' share ``E_others`` is a constant to it (paper Alg. 1, l. 14)."""
    E = E_others + embed(party, tokens, m, lowp) / C
    return xent(logits(party, E, m, lowp), labels)


def sgd_follow(parties, batches, m: dict, *, lr: float, grad_clip: float,
               store_dtype: str, lowp: Optional[str] = None) -> dict:
    """``len(batches)`` SGD steps of the whole federation.

    Every party takes the gradient of its own loss, in float32; the update
    stores the parameters in ``store_dtype``, the configuration's. The
    reference follows SGD without clipping: ``grad_clip`` > 0 is refused.

    Returns per-step per-party losses ``(steps, C)``, per party and leaf
    the norm of the first gradient as the update applies it, and the
    parties' parameters after the last step.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    if grad_clip > 0:
        raise ValueError("the reference follows SGD without clipping; "
                         f"the configuration clips at {grad_clip}")
    C = len(parties)
    st = jnp.dtype(store_dtype)
    ps = list(parties)
    with jax.default_matmul_precision("highest"):
        to32 = jax.jit(lambda p: jax.tree.map(
            lambda a: a.astype(jnp.float32), p))
        emb = jax.jit(lambda p, t: embed(p, t, m, lowp))
        vg = jax.jit(jax.value_and_grad(
            lambda p, t, l, eo: party_loss(p, t, l, eo, C, m, lowp)))
        upd = jax.jit(lambda p, g: jax.tree.map(
            lambda a, b: (a - lr * b).astype(st), p, g))
        sq_norms = jax.jit(lambda g: [jnp.sum(jnp.square(x))
                                      for x in jax.tree.leaves(g)])
        losses, grad1 = [], None
        for b in batches:
            tok, lab = jnp.asarray(b["tokens"]), jnp.asarray(b["labels"])
            Es = [emb(p, tok) for p in ps]
            E = sum(Es) / C
            per, norms = [], []
            for k in range(C):
                loss, g = vg(to32(ps[k]), tok, lab, E - Es[k] / C)
                per.append(float(loss))
                norms.append([math.sqrt(float(x))
                              for x in jax.device_get(sq_norms(g))])
                ps[k] = upd(to32(ps[k]), g)
                del g
            losses.append(per)
            if grad1 is None:
                grad1 = norms
            del Es, E
    return {"loss": np.asarray(losses, np.float64), "grad1": grad1,
            "params": ps}


def change_norms(after, before) -> list:
    """Per party and leaf, the norm of the parameters' change."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a, b: [jnp.linalg.norm(
        x.astype(jnp.float32) - y.astype(jnp.float32))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])
    return [[float(v) for v in jax.device_get(f(a, b))]
            for a, b in zip(after, before)]
