"""Fused blind + aggregate Pallas kernel (the paper's Eq. 6 + Eq. 7).

Computes E = (E_a + sum_k (E_k + r_k)) / C in a single VMEM pass over
(token x d_embed) tiles — the blinded per-party embeddings are never
materialized in HBM (beyond-paper fusion; the reference path materializes
[E_k] explicitly the way the paper's protocol transmits them).

The party dim K is *tiled* (``block_k``): each grid step reduces a
(bk, bn, bd) slab into a float32 VMEM accumulator, so VMEM holds
O(block_k x bn x bd) regardless of K — the seed kernel kept K whole per
tile, which stopped fitting once the vectorized party engine pushed
federations past the paper's C = 4 (K = 64+ at 256x128 tiles is >8 MB).

The kernel carries a ``jax.custom_vjp``: aggregation is linear with
dE/dE_a = dE/dE_k = dE/dr_k = 1/C, so the backward pass is one fused
broadcast kernel emitting every party's gE / C pullback in a single pass
(this is exactly the per-party embedding-net loss signal of Alg. 1 line 14;
see core/protocol.py). Without it, jax.grad of a pallas_call is undefined.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _largest_divisor(n: int, cap: int) -> int:
    b = max(1, min(cap, n))
    while n % b:
        b -= 1
    return b


def _fwd_kernel(ea_ref, ep_ref, m_ref, o_ref, acc_ref, *, inv_c: float,
                gk: int):
    kk = pl.program_id(2)
    part = jnp.sum(ep_ref[...].astype(jnp.float32)
                   + m_ref[...].astype(jnp.float32), axis=0)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = ea_ref[...].astype(jnp.float32) + part

    @pl.when(kk > 0)
    def _acc():
        acc_ref[...] += part

    @pl.when(kk == gk - 1)
    def _fin():
        o_ref[...] = (acc_ref[...] * inv_c).astype(o_ref.dtype)


def _bwd_kernel(g_ref, dea_ref, dep_ref, *, inv_c: float):
    kk = pl.program_id(2)
    g = g_ref[...].astype(jnp.float32) * inv_c       # (bn, bd)

    @pl.when(kk == 0)
    def _active():
        dea_ref[...] = g.astype(dea_ref.dtype)

    bk = dep_ref.shape[0]
    dep_ref[...] = jnp.broadcast_to(g[None], (bk,) + g.shape).astype(
        dep_ref.dtype)


def _tile(n: int, cap: int, align: int):
    """(block, padded n) for one tiled dim. The whole dim when it fits in
    ``cap``; otherwise n is rounded up to a multiple of ``align`` and the
    block is the largest multiple of ``align``, at most ``cap``, that
    divides it. Mosaic accepts a block whose last two dims are aligned
    multiples or the whole array dims, nothing else."""
    if n <= cap:
        return n, n
    n_pad = -(-n // align) * align
    b = max(align, cap - cap % align)
    while n_pad % b:
        b -= align
    return b, n_pad


def _blocks(N: int, d: int, K: int, block_n: int, block_d: int,
            block_k: int, itemsize: int):
    """Tile sizes and padded (N, d). The second-minor block is a multiple
    of the dtype's sublane tile (8 rows of 32-bit words; 16 for bf16) and
    the minor block a multiple of 128 lanes, unless it spans the dim."""
    sub = 8 * max(1, 4 // itemsize)
    bn, n_pad = _tile(N, block_n, sub)
    bd, d_pad = _tile(d, block_d, 128)
    return bn, bd, _largest_divisor(K, block_k), n_pad, d_pad


def _pad_to(x, n_pad: int, d_pad: int):
    """Zero-pad the trailing (N, d) dims; zeros add nothing to the sum."""
    N, d = x.shape[-2:]
    if (N, d) == (n_pad, d_pad):
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2)
                   + [(0, n_pad - N), (0, d_pad - d)])


def _min_itemsize(*dtypes) -> int:
    return min(jnp.dtype(t).itemsize for t in dtypes)


def _bcast_bwd(g, K: int, dep_dtype, block_n: int, block_d: int,
               block_k: int, interpret: bool):
    """Backward of the (linear) aggregation: every party's cotangent is
    g / C, emitted by one broadcast kernel. Returns (dea, dep)."""
    N, d = g.shape
    bn, bd, bk, n_pad, d_pad = _blocks(
        N, d, K, block_n, block_d, block_k,
        _min_itemsize(g.dtype, dep_dtype))
    dea, dep = pl.pallas_call(
        functools.partial(_bwd_kernel, inv_c=1.0 / (K + 1)),
        grid=(n_pad // bn, d_pad // bd, K // bk),
        in_specs=[pl.BlockSpec((bn, bd), lambda i, j, k: (i, j))],
        out_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, k: (i, j)),
            pl.BlockSpec((bk, bn, bd), lambda i, j, k: (k, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, d_pad), g.dtype),
            jax.ShapeDtypeStruct((K, n_pad, d_pad), dep_dtype),
        ],
        interpret=interpret,
    )(_pad_to(g, n_pad, d_pad))
    return dea[:N, :d], dep[:, :N, :d]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _blind_agg(ea, ep, mk, dtypes, block_n, block_d, block_k, interpret,
               n_passive):
    """ea (N, d); ep/mk (K, N, d) -> (N, d). Differentiable (custom VJP).

    ``dtypes``/``n_passive`` duplicate static facts about ep/mk so the
    backward rule can rebuild cotangent avals without array residuals.
    """
    K, N, d = ep.shape
    bn, bd, bk, n_pad, d_pad = _blocks(
        N, d, K, block_n, block_d, block_k,
        _min_itemsize(ea.dtype, ep.dtype, mk.dtype))
    grid = (n_pad // bn, d_pad // bd, K // bk)   # k innermost: output
    out = pl.pallas_call(                        # block done before moving on
        functools.partial(_fwd_kernel, inv_c=1.0 / (K + 1), gk=K // bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, k: (i, j)),
            pl.BlockSpec((bk, bn, bd), lambda i, j, k: (k, i, j)),
            pl.BlockSpec((bk, bn, bd), lambda i, j, k: (k, i, j)),
        ],
        out_specs=pl.BlockSpec((bn, bd), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d_pad), ea.dtype),
        scratch_shapes=[pltpu.VMEM((bn, bd), jnp.float32)],
        interpret=interpret,
    )(_pad_to(ea, n_pad, d_pad), _pad_to(ep, n_pad, d_pad),
      _pad_to(mk, n_pad, d_pad))
    return out[:N, :d]


def _blind_agg_fwd(ea, ep, mk, dtypes, block_n, block_d, block_k, interpret,
                   n_passive):
    out = _blind_agg(ea, ep, mk, dtypes, block_n, block_d, block_k,
                     interpret, n_passive)
    return out, None


def _blind_agg_bwd(dtypes, block_n, block_d, block_k, interpret, n_passive,
                   res, g):
    ep_dtype, mk_dtype = dtypes
    dea, dep = _bcast_bwd(g, n_passive, ep_dtype, block_n, block_d,
                          block_k, interpret)
    return dea, dep, dep.astype(mk_dtype)


_blind_agg.defvjp(_blind_agg_fwd, _blind_agg_bwd)


def blind_agg(E_active: jnp.ndarray, E_passive: jnp.ndarray,
              masks: jnp.ndarray, *, block_n: int = 256, block_d: int = 128,
              block_k: int = 8, interpret: bool = False) -> jnp.ndarray:
    """E_active (..., d); E_passive/masks (K, ..., d). Returns (..., d)."""
    K = E_passive.shape[0]
    orig_shape = E_active.shape
    d = orig_shape[-1]
    N = E_active.size // d
    ea = E_active.reshape(N, d)
    ep = E_passive.reshape(K, N, d)
    mk = masks.reshape(K, N, d)
    out = _blind_agg(ea, ep, mk, (ep.dtype, mk.dtype), block_n, block_d,
                     block_k, interpret, int(K))
    return out.reshape(orig_shape)


# ---------------------------------------------------------------------------
# pltpu-PRNG variant: in-kernel mask synthesis (no (K, N, d) mask HBM tensor)
# ---------------------------------------------------------------------------


_GOLDEN = -0x61C88647                 # 0x9E3779B9 as a wrapped int32


def _fmix32(h):
    """murmur3's 32-bit finalizer on int32 words (wrapping multiplies): a
    bijection, so distinct inputs keep distinct outputs."""
    srl = jax.lax.shift_right_logical
    h = h ^ srl(h, jnp.int32(16))
    h = h * jnp.int32(-0x7A143595)      # 0x85EBCA6B
    h = h ^ srl(h, jnp.int32(13))
    h = h * jnp.int32(-0x3D4D51CB)      # 0xC2B2AE35
    return h ^ srl(h, jnp.int32(16))


def _prng_fwd_kernel(rmix_ref, sh_ref, sl_ref, sg_ref, ea_ref, ep_ref, o_ref,
                     acc_ref, *, inv_c: float, gk: int, n_pairs: int,
                     scale: float):
    """Blind + aggregate with masks generated by the per-core TPU PRNG.

    For each party row p of the slab, its Eq. 5 mask is re-derived pair by
    pair. The PRNG takes two seed words, folded from the pair seed, the
    round and the output tile (``_fmix32``, see make_prng_blind_agg), so
    BOTH endpoints of a pair emit the identical (bn, bd) stream for a given
    output tile and their ±1-signed contributions cancel in the fp32
    accumulator — the mask tensor never exists outside VMEM/registers.
    Masks are uniform on [-scale/2, scale/2) via the mantissa bitcast trick
    (distribution differs from the HBM path's normals; cancellation — the
    protocol invariant — is what tests pin down).
    """
    ii, jj, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ep = ep_ref[...].astype(jnp.float32)            # (bk, bn, bd)
    bk, bn, bd = ep.shape
    part = jnp.sum(ep, axis=0)
    tile = _fmix32((ii * pl.num_programs(1) + jj) ^ rmix_ref[1])
    for p in range(bk):                             # static party unroll
        row = (kk * bk + p) * n_pairs

        def pair_body(j, acc, row=row):
            pltpu.prng_seed(sh_ref[row + j] ^ rmix_ref[0],
                            sl_ref[row + j] ^ tile)
            bits = pltpu.bitcast(pltpu.prng_random_bits((bn, bd)),
                                 jnp.uint32)
            # mantissa trick: top 23 random bits -> f32 in [1, 2), recenter
            u = pltpu.bitcast((bits >> 9) | jnp.uint32(0x3F800000),
                              jnp.float32) - 1.5
            s = sg_ref[row + j].astype(jnp.float32) * scale
            return acc + s * u

        part = jax.lax.fori_loop(0, n_pairs, pair_body, part)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = ea_ref[...].astype(jnp.float32) + part

    @pl.when(kk > 0)
    def _acc():
        acc_ref[...] += part

    @pl.when(kk == gk - 1)
    def _fin():
        o_ref[...] = (acc_ref[...] * inv_c).astype(o_ref.dtype)


def make_prng_blind_agg(seed_hi, seed_lo, signs, *, block_n: int = 256,
                        block_d: int = 128, block_k: int = 8,
                        mask_scale: float = 1.0, interpret: bool = False):
    """Build a fused blind+aggregate fn with IN-KERNEL mask synthesis.

    seed_hi/seed_lo/signs: host (K, K-1) arrays — the MaskEngine's packed
    pair-seed layout. They are baked into the returned callable as
    compile-time constants, passed whole to SMEM, exactly like the
    federation's DH ceremony fixes them once.

    ``pltpu.prng_seed`` takes two words, so the six facts that key a mask
    tile — the pair seed (hi, lo), the round and the tile coordinates —
    are folded into two: ``hi ^ fmix(round)`` and
    ``lo ^ fmix(tile ^ fmix(round ^ golden))``. Both endpoints of a pair
    hold the same seed words and compute the same fold. For one pair the
    map (round, tile) -> words is injective (fmix is a bijection), so no
    two rounds or tiles of a pair share a stream.

    Returns ``fn(ea (N, d), ep (K, N, d), rnd_words_f32 (2,)) -> (N, d)``
    carrying a custom VJP (aggregation is linear; masks are seed-derived
    constants, so the backward pass is the same fused gE/C broadcast
    kernel as blind_agg). The round index travels as two f32 words, each
    < 2^16 and therefore exact in f32 (a single f32 scalar would silently
    round the >= 2^30 SERVE/PREFILL_DOMAIN offsets, collapsing distinct
    rounds onto one PRNG stream) — floats so every differentiable
    argument has a float cotangent; use ``round_words`` to build them.

    TPU-only numerics: ``pltpu.prng_*`` has no CPU interpret rule in this
    jax version — off-TPU callers use ops.blind_agg_prng, which falls back
    to the MaskEngine graph path.
    """
    K, n_pairs = np.shape(seed_hi)
    # flat int32 words: 1-D SMEM tables avoid the 2-D (8, 128) tiling
    tables = [np.ascontiguousarray(t, np.uint32).view(np.int32).reshape(-1)
              for t in (seed_hi, seed_lo)]
    tables.append(np.ascontiguousarray(signs, np.int32).reshape(-1))

    @jax.custom_vjp
    def fused(ea, ep, rnd_words_f32):
        N, d = ea.shape
        bn, bd, bk, n_pad, d_pad = _blocks(
            N, d, K, block_n, block_d, block_k,
            _min_itemsize(ea.dtype, ep.dtype))
        grid = (n_pad // bn, d_pad // bd, K // bk)
        w = jnp.asarray(rnd_words_f32, jnp.float32).reshape(2)
        r = (w[0].astype(jnp.int32) << 15) | w[1].astype(jnp.int32)
        rmix = jnp.stack([_fmix32(r), _fmix32(r ^ jnp.int32(_GOLDEN))])
        smem = pl.BlockSpec(memory_space=pltpu.SMEM)
        out = pl.pallas_call(
            functools.partial(_prng_fwd_kernel, inv_c=1.0 / (K + 1),
                              gk=K // bk, n_pairs=n_pairs,
                              scale=float(mask_scale)),
            grid=grid,
            in_specs=[
                smem, smem, smem, smem,
                pl.BlockSpec((bn, bd), lambda i, j, k: (i, j)),
                pl.BlockSpec((bk, bn, bd), lambda i, j, k: (k, i, j)),
            ],
            out_specs=pl.BlockSpec((bn, bd), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((n_pad, d_pad), ea.dtype),
            scratch_shapes=[pltpu.VMEM((bn, bd), jnp.float32)],
            interpret=interpret,
        )(rmix, *[jnp.asarray(t) for t in tables],
          _pad_to(ea, n_pad, d_pad), _pad_to(ep, n_pad, d_pad))
        return out[:N, :d]

    def fused_fwd(ea, ep, rnd_words_f32):
        # scalar zero residual only carries ep's dtype for the cotangent aval
        return fused(ea, ep, rnd_words_f32), jnp.zeros((), ep.dtype)

    def fused_bwd(res, g):
        dea, dep = _bcast_bwd(g, K, res.dtype, block_n, block_d, block_k,
                              interpret)
        return dea, dep, jnp.zeros((2,), jnp.float32)

    fused.defvjp(fused_fwd, fused_bwd)
    return fused


def round_words(round_idx) -> jnp.ndarray:
    """Split a round index (< 2^31) into two f32 words, each < 2^16 and
    therefore exactly representable — the wire format make_prng_blind_agg
    expects for its round argument."""
    r = jnp.asarray(round_idx, jnp.int32)
    return jnp.stack([(r >> 15).astype(jnp.float32),
                      (r & 0x7FFF).astype(jnp.float32)])


def prng_blind_agg(E_active: jnp.ndarray, E_passive: jnp.ndarray, engine,
                   round_idx, *, mask_scale: float = 1.0,
                   block_n: int = 256, block_d: int = 128, block_k: int = 8,
                   interpret: bool = False) -> jnp.ndarray:
    """Fused blind+aggregate from a blinding.MaskEngine's seed layout.

    E_active (..., d); E_passive (K, ..., d). Masks are synthesized inside
    the kernel (see make_prng_blind_agg) — no (K, ..., d) mask HBM tensor.
    """
    K = E_passive.shape[0]
    orig_shape = E_active.shape
    d = orig_shape[-1]
    N = E_active.size // d
    fn = make_prng_blind_agg(engine.seed_hi, engine.seed_lo, engine.signs,
                             block_n=block_n, block_d=block_d,
                             block_k=block_k, mask_scale=mask_scale,
                             interpret=interpret)
    out = fn(E_active.reshape(N, d), E_passive.reshape(K, N, d),
             round_words(round_idx))
    return out.reshape(orig_shape)
