"""Operations and bytes the algorithm needs, counted from shapes.

These are model operations: what the mathematics requires, not what a
program happens to execute. Recomputation (remat) is not counted, and a
matrix product of ``(m, k) @ (k, n)`` counts ``2 m k n``. A training step
counts three times its forward pass (the backward pass takes two).
"""
from __future__ import annotations

from typing import Sequence


def mlp_forward(batch: int, dims: Sequence[int]) -> float:
    """A stack of dense layers ``dims[0] -> dims[1] -> ...``."""
    return sum(2.0 * batch * a * b for a, b in zip(dims[:-1], dims[1:]))


def classifier_round(cfg: dict, batch: int) -> float:
    """One EASTER classifier training round: every party's embedding and
    decision nets, forward and backward."""
    C = cfg["parties"]
    mlps = cfg["party_mlps"]
    F = cfg["features"]["n_features"]
    total = 0.0
    for k in range(C):
        m = mlps[k % len(mlps)]
        nf = F // C + (1 if k < F % C else 0)
        total += mlp_forward(batch, [nf, *m["embed"], cfg["d_embed"]])
        total += mlp_forward(batch, [cfg["d_embed"], *m["decide"],
                                     cfg["n_classes"]])
    return 3.0 * total


# -- the language-model federation (configuration keys as Qwen2's) ---------

def attn_flops(m: dict, tokens: int, context: float) -> float:
    """Projections plus ``QK^T`` and ``PV`` for ``tokens`` queries that
    each attend to ``context`` keys on average (the forms of
    benchmarks/costmodel.py)."""
    d, hd = m["hidden_size"], m["head_dim"]
    H, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    proj = 2.0 * tokens * d * hd * (H + 2 * Hkv) + 2.0 * tokens * H * hd * d
    return proj + 2.0 * 2.0 * tokens * context * H * hd


def swiglu_flops(d: int, d_ff: int, tokens: int) -> float:
    return 2.0 * tokens * d * d_ff * 3


def party_depths(m: dict) -> list:
    """Active party at the configured depth, passives at the fraction."""
    e, n = m["easter"], m["num_hidden_layers"]
    p = max(2, int(round(n * e["passive_depth_frac"])))
    return [n] + [p] * e["num_passive"]


def embed_flops(m: dict, layers: int, tokens: int, context: float) -> float:
    """One party's backbone and projection into the shared space."""
    per = attn_flops(m, tokens, context) + swiglu_flops(
        m["hidden_size"], m["intermediate_size"], tokens)
    return layers * per + 2.0 * tokens * m["hidden_size"] * \
        m["easter"]["d_embed"]


def decide_flops(m: dict, tokens: int) -> float:
    """One party's decision blocks and LM head."""
    de = m["easter"]["d_embed"]
    return m["easter"]["decision_layers"] * swiglu_flops(de, 4 * de, tokens) \
        + 2.0 * tokens * de * m["vocab_size"]


def lm_train_round(m: dict, batch: int, seq: int) -> float:
    """One training step of the federation: every party embeds, decides
    and takes its loss; forward and backward."""
    T, ctx = batch * seq, (seq + 1) / 2.0
    fwd = sum(embed_flops(m, n, T, ctx) + decide_flops(m, T)
              for n in party_depths(m))
    return 3.0 * fwd


def lm_prefill(m: dict, tokens: int) -> float:
    """A prompt's cache-building pass: every party embeds (no decision)."""
    return sum(embed_flops(m, n, tokens, (tokens + 1) / 2.0)
               for n in party_depths(m))


def lm_decode_token(m: dict, context: float) -> float:
    """One served token: every party embeds it against its cache, the
    active party decides."""
    return sum(embed_flops(m, n, 1, context)
               for n in party_depths(m)) + decide_flops(m, 1)


def _type_bytes(m: dict) -> int:
    return 2 if m["torch_dtype"] == "bfloat16" else 4


def lm_decode_weight_bytes(m: dict) -> float:
    """Bytes of the weights one decode round reads once, at the
    configuration's type: every party's backbone layers, final norm and
    projection into the shared space, and the active party's decision net,
    final norm and LM head (only the active party decides). The token
    embedding tables are not read whole: see ``lm_embed_row_bytes``."""
    d, hd, de = m["hidden_size"], m["head_dim"], m["easter"]["d_embed"]
    H, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    attn = d * hd * (H + 2 * Hkv) + hd * (H + 2 * Hkv) + H * hd * d
    block = attn + 3 * d * m["intermediate_size"] + 2 * d
    decide = m["easter"]["decision_layers"] * (3 * de * 4 * de + de) \
        + de + de * m["vocab_size"]
    n = sum(nl * block + d + d * de for nl in party_depths(m)) + decide
    return float(_type_bytes(m) * n)


def lm_embed_row_bytes(m: dict) -> float:
    """Bytes of one served token's embedding rows, one in every party's
    token table."""
    return float(len(party_depths(m)) * m["hidden_size"] * _type_bytes(m))


def lm_kv_bytes_per_token(m: dict) -> float:
    """Bytes of one position's keys and values over every party's layers."""
    return sum(n for n in party_depths(m)) * 2 * m["num_key_value_heads"] \
        * m["head_dim"] * _type_bytes(m)


# -- the blind_agg kernel (ready for when a cell runs it) ---------------------

def blind_agg(parties: int, rows: int, width: int) -> tuple:
    """(operations, bytes) of one blinded aggregation: the K passive
    embeddings plus their masks and the active one are read once (fp32),
    the mean written once; one add per mask, one per party, one scale."""
    K = parties - 1
    n = rows * width
    ops = n * (2 * K + 1)
    nbytes = 4 * n * (2 * K + 1 + 1)
    return float(ops), float(nbytes)
