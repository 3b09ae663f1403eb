"""Inputs made from the seed: the general generators the traffic files drive.

Every generator takes a ``numpy.random.Generator`` built from the seed and
the parameters of a traffic file, and nothing else, so the same seed gives
the same inputs. The sizes a seed draws come from the traffic file; the
seed only draws values, so every seed does the same amount of work.
"""
from __future__ import annotations

import math
from typing import List, Sequence, Tuple

import numpy as np


# -- vertically split classification rows ------------------------------------

def mixture_rows(rng, n: int, *, n_features: int, n_classes: int,
                 image_hw: Sequence[int], n_groups: int, moduli: Sequence[int],
                 sep: float, noise: float) -> Tuple[np.ndarray, np.ndarray]:
    """Gaussian-mixture rows laid out as an image split into column groups.

    Group p of the columns tells apart only the class modulo ``moduli[p]``,
    so no party's vertical slice sees the whole class: the regime of the
    paper's vertical split. Features are standardized over the rows drawn.
    """
    h, w = image_hw
    assert h * w == n_features, (image_hw, n_features)
    cols = np.array_split(np.arange(w), n_groups)
    groups = [np.concatenate([np.arange(h) * w + c for c in cg])
              for cg in cols]
    basis = rng.normal(0, 1.0, (8, n_features))
    mus = [rng.normal(0, sep, (moduli[p], len(g)))
           for p, g in enumerate(groups)]
    y = rng.integers(0, n_classes, n).astype(np.int32)
    x = rng.normal(0, noise, (n, n_features))
    for p, g in enumerate(groups):
        x[:, g] += mus[p][y % moduli[p]]
    x += rng.normal(0, 1.0, (n, 8)) @ basis * 0.3
    x = (x - x.mean(0)) / (x.std(0) + 1e-6)
    return x.astype(np.float32), y


def split_sizes(n_features: int, parties: int) -> List[int]:
    """Near-equal vertical slices, the first ``n_features % parties`` one
    wider (the paper's vertical split)."""
    return [n_features // parties + (1 if i < n_features % parties else 0)
            for i in range(parties)]


def split_columns(x: np.ndarray, parties: int) -> List[np.ndarray]:
    out, off = [], 0
    for s in split_sizes(x.shape[-1], parties):
        out.append(np.ascontiguousarray(x[..., off:off + s]))
        off += s
    return out


# -- language-model tokens ----------------------------------------------------

def zipf_tokens(rng, shape, vocab: int, exponent: float) -> np.ndarray:
    """Token ids drawn with probability proportional to 1 / rank^exponent."""
    p = 1.0 / np.arange(1, vocab + 1) ** exponent
    p /= p.sum()
    return rng.choice(vocab, size=shape, p=p).astype(np.int32)


def uniform_tokens(rng, shape, vocab: int, exclude: Sequence[int] = ()
                   ) -> np.ndarray:
    ids = rng.integers(0, vocab, size=shape).astype(np.int32)
    for e in exclude:
        ids[ids == e] = (e + 1) % vocab
    return ids


# -- request lengths ----------------------------------------------------------

def lognormal_ints(rng, n: int, *, median: float, sigma: float, lo: int,
                   hi: int) -> np.ndarray:
    """Integers from a lognormal with the given median, clipped to [lo, hi]."""
    v = np.exp(rng.normal(math.log(median), sigma, n))
    return np.clip(np.ceil(v), lo, hi).astype(np.int64)


def bucket_up(values: np.ndarray, buckets: Sequence[int]) -> np.ndarray:
    """Each value rounded up to the next bucket (the largest caps it)."""
    b = np.asarray(sorted(buckets))
    idx = np.minimum(np.searchsorted(b, values, side="left"), len(b) - 1)
    return b[idx]
