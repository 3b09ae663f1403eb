"""Diffie–Hellman key exchange + pairwise blinding factors (paper §IV-B).

Host-side crypto uses Python big-int modular exponentiation over the RFC-3526
2048-bit MODP group (group 14), generator g = 2, and SHA-256 as the
collusion-resistant hash H(.) of the paper. Shared keys seed an in-graph PRF
(``jax.random``) that expands to per-element masks.

Three mask modes:
  * ``float``  — paper-faithful real-valued masks. Each pair's masks are
    identical arrays with opposite signs, so cancellation is bit-exact for
    K = 2 (a + (-a) == 0); for K >= 3 fp non-associativity across parties'
    partial sums leaves ~1 ulp residual (measured in tests).
  * ``int32``  — beyond-paper hardening: embeddings are fixed-point-quantized
    (static scale 2^16) and masked in the ring Z_2^32 (uniform masks,
    wrap-around add), the standard secure-aggregation construction;
    cancellation is exact by ring arithmetic.
  * ``int8``   — the narrow-ring wire format: the same construction in
    Z_2^8 with a PER-ROUND dynamic scale (``ring_scale``) chosen so the
    TRUE sum of all C quantized embeddings fits in [-127, 127]; each ring
    element ships as one byte (packed 4-per-int32 word on the wire,
    ``pack_int8_words``) plus one fp32 scale scalar per leg. Blinding
    stays information-theoretic — masks are uniform over Z_2^8 — and
    cancellation is exact mod 256.

``fresh_masks``: the paper's r_k is static across rounds; we fold the round
counter into the PRF by default (strictly stronger; set fresh=False for the
paper-literal behaviour). ``scalar=True`` reproduces the paper's literal
Eq. (5) (one scalar blinding factor per party) instead of per-element masks.

Mask synthesis engines:
  * ``MaskEngine`` — the production path. The K·(K-1) pairwise seeds are
    packed ONCE at setup into (K, K-1) uint32 word matrices plus a signed
    ±1 coefficient matrix; per round, every pair mask is generated by a
    single nested-``jax.vmap`` PRF call and the per-party sums are taken by
    one ``lax.scan`` left-fold over the pair axis. The whole synthesis is
    O(1) traced XLA ops regardless of K (vs the loop oracle's O(K²) traced
    ops, which dominated round setup at C = 128). The scan fold replays the
    loop oracle's exact addition order, so for the fixed ascending-j seed
    layout the two paths are BIT-EXACT in both float and int32 modes
    (proven in tests/test_blinding.py).
  * ``party_mask`` / ``all_party_masks`` — the seed's per-party Python
    double loop, kept as the equivalence oracle.
  * ``FusedMasks`` — marker routing aggregation to the pltpu-PRNG Pallas
    kernel (repro.kernels.blind_agg.make_prng_blind_agg), which synthesizes
    the pair masks inside the kernel so the (K, B, d) mask tensor is never
    materialized in HBM (TPU only; off-TPU it falls back to MaskEngine).
"""
from __future__ import annotations

import functools
import hashlib
import secrets
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# RFC 3526, group 14 (2048-bit MODP). DLP assumed hard (paper §II-B).
P_HEX = (
    "FFFFFFFFFFFFFFFFC90FDAA22168C234C4C6628B80DC1CD129024E088A67CC74"
    "020BBEA63B139B22514A08798E3404DDEF9519B3CD3A431B302B0A6DF25F1437"
    "4FE1356D6D51C245E485B576625E7EC6F44C42E9A637ED6B0BFF5CB6F406B7ED"
    "EE386BFB5A899FA5AE9F24117C4B1FE649286651ECE45B3DC2007CB8A163BF05"
    "98DA48361C55D39A69163FA8FD24CF5F83655D23DCA3AD961C62F356208552BB"
    "9ED529077096966D670C354E4ABC9804F1746C08CA18217C32905E462E36CE3B"
    "E39E772C180E86039B2783A2EC07A28FB5C55DF06F4C52C9DE2BCBF695581718"
    "3995497CEA956AE515D2261898FA051015728E5A8AACAA68FFFFFFFFFFFFFFFF")
PRIME = int(P_HEX, 16)
GENERATOR = 2


@dataclass(frozen=True)
class KeyPair:
    sk: int
    pk: int


def keygen(rng: secrets.SystemRandom | None = None, *,
           _test_seed: int | None = None) -> KeyPair:
    """Generate (SK, PK = g^SK mod p). ``_test_seed`` for deterministic tests."""
    if _test_seed is not None:
        sk = int.from_bytes(hashlib.sha256(
            _test_seed.to_bytes(8, "big")).digest(), "big") % (PRIME - 2) + 1
    else:
        sk = (rng or secrets.SystemRandom()).randrange(2, PRIME - 1)
    return KeyPair(sk=sk, pk=pow(GENERATOR, sk, PRIME))


def shared_key(sk_k: int, pk_j: int) -> bytes:
    """CK_{k,j} = H((PK_j)^{SK_k}) — symmetric by construction (Eq. 4)."""
    s = pow(pk_j, sk_k, PRIME)
    return hashlib.sha256(s.to_bytes((s.bit_length() + 7) // 8 or 1,
                                     "big")).digest()


def prf_seed(ck: bytes) -> int:
    """H(CK) -> 63-bit PRF seed (the paper's H(CK_{k,j}) term of Eq. 5)."""
    return int.from_bytes(hashlib.sha256(ck + b"easter-mask").digest()[:8],
                          "big") >> 1


def pairwise_seeds(keys: Sequence[KeyPair]) -> Dict[Tuple[int, int], int]:
    """All passive-party pair seeds. seeds[(k, j)] == seeds[(j, k)].

    CK_{k,j} is symmetric (Eq. 4), so each unordered pair needs ONE
    2048-bit modexp — K(K-1)/2 total, not the K(K-1) a naive double loop
    costs (35 s vs 70 s of host time at C = 128). In the deployed protocol
    each party computes only its own row; this is the simulation's
    ceremony."""
    K = len(keys)
    seeds = {}
    for k in range(K):
        for j in range(k + 1, K):
            s = prf_seed(shared_key(keys[k].sk, keys[j].pk))
            seeds[(k, j)] = seeds[(j, k)] = s
    return seeds


def seed_words(seed: int) -> Tuple[np.uint32, np.uint32]:
    """Split a 63-bit PRF seed into (hi, lo) 32-bit words, losslessly."""
    return np.uint32(seed >> 32), np.uint32(seed & 0xFFFFFFFF)


# PRF domain separation (effective with fresh_masks=True, the default):
# decode rounds live at SERVE_DOMAIN + pos and prompt-phase rounds at
# PREFILL_DOMAIN + request nonce, so an inference-time mask can never
# coincide with a training-round mask (or a decode mask with a prefill
# mask) of the same shape. Every party can compute the offsets, so
# cross-trust-domain cancellation is unaffected. Training rounds must
# stay below 2**30; decode positions and prefill nonces below 2**29.
# fresh_masks=False is the paper-literal STATIC-mask mode: one pad per
# party for the whole deployment — domain tags and nonces are then
# deliberately collapsed, and pad reuse (with the embedding-difference
# leakage it implies) is that mode's documented semantics.
SERVE_DOMAIN = 1 << 30
PREFILL_DOMAIN = SERVE_DOMAIN | (1 << 29)

# Batched serving (continuous batching, core/serving.py): R concurrent
# request lanes decode in ONE protocol round, so two lanes at the same
# sequence position must not land on the same PRF round (pad reuse across
# lanes would leak their embedding difference, exactly the fresh_masks
# rationale applied across requests instead of across time). Each request
# gets a scheduler-issued nonce and its decode round is
#     SERVE_DOMAIN + nonce * SERVE_NONCE_STRIDE + pos
# with pos < SERVE_NONCE_STRIDE, so distinct nonces occupy disjoint round
# windows inside the 2**29-wide serve span (nonce < 2**14). Prefill keeps
# its own domain: PREFILL_DOMAIN + nonce. Single-request serving
# (nonce = 0) degenerates to the legacy SERVE_DOMAIN + pos schedule.
SERVE_NONCE_STRIDE = 1 << 15
MAX_SERVE_NONCE = (1 << 14) - 1


def serve_round(nonce, pos):
    """Per-lane decode-round PRF index for batched serving (see above).

    Both args may be scalars or (R,) lane vectors; a vector result routes
    every mask path through the per-lane PRF vmap in _mask_from_words."""
    nonce = jnp.asarray(nonce, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    return SERVE_DOMAIN + nonce * SERVE_NONCE_STRIDE + pos


# the ring (integer) wire formats; "float" is the paper-faithful mode.
# Ring masks are uniform over Z_2^w and dense, so top-k compression does
# not compose with them (see EasterClassifier.__post_init__).
RING_MODES = ("int32", "int8")


def mask_dtype(mode: str):
    """Element dtype of a mask / blinded-uplink array for a wire mode."""
    if mode == "int32":
        return jnp.int32
    if mode == "int8":
        return jnp.int8
    return jnp.float32


def _mask_from_words(hi, lo, round_idx, mshape, mode: str) -> jnp.ndarray:
    """The ONE PRF construction every mask path derives from: key the PRNG
    by the full 63-bit pair seed as two 32-bit ``fold_in`` words (the seed
    path used to truncate with ``% 2**31``, halving entropy and inviting
    birthday collisions across the K·(K-1)/2 pairs at large K), fold in
    the round, expand to ``mshape``.

    Shared by pair_mask (loop oracle + wire.py's per-process parties) and
    MaskEngine (batched under vmap): both ends of a pair, whichever engine
    synthesizes them, must derive the IDENTICAL array for cancellation.

    ``round_idx`` may be an (R,) vector of per-lane rounds (batched
    serving); ``mshape`` then leads with the lane axis R and each lane's
    slice is drawn from its own fold-in — bit-exact vs R separate scalar
    calls of shape ``mshape[1:]`` (the threefry draw depends only on the
    per-lane element count, which matches by construction)."""
    round_idx = jnp.asarray(round_idx)
    if round_idx.ndim == 1:
        assert mshape and mshape[0] == round_idx.shape[0], (
            f"per-lane rounds {round_idx.shape} need a leading lane axis "
            f"on mshape, got {mshape}")
        return jax.vmap(
            lambda r: _mask_from_words(hi, lo, r, mshape[1:], mode)
        )(round_idx)
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(hi), lo), round_idx)
    if mode == "int32":
        return jax.random.randint(key, mshape, jnp.iinfo(jnp.int32).min,
                                  jnp.iinfo(jnp.int32).max, jnp.int32)
    if mode == "int8":
        # exactly uniform over Z_2^8: 8 raw PRF bits per element; the
        # uint8 -> int8 astype is a two's-complement reinterpretation
        # (bit-preserving), so the int8 view stays uniform over the ring
        return jax.random.bits(key, mshape, jnp.uint8).astype(jnp.int8)
    return jax.random.normal(key, mshape, jnp.float32)


def pair_mask(seed: int, shape, round_idx=0, mode: str = "float",
              scalar: bool = False) -> jnp.ndarray:
    """PRF(CK_{k,j}, round) expanded to one pair's mask."""
    hi, lo = seed_words(seed)
    return _mask_from_words(hi, lo, round_idx, () if scalar else shape,
                            mode)


def party_mask(k: int, n_passive: int, seeds: Dict[Tuple[int, int], int],
               shape, round_idx=0, mode: str = "float",
               scalar: bool = False, scale: float = 1.0) -> jnp.ndarray:
    """r_{l_k} = sum_j (-1)^{k>j} PRF(CK_{k,j})  (Eq. 5, per-element form).

    Loop oracle: O(K) traced PRF ops per party. Production mask synthesis
    is ``MaskEngine`` (bit-exact equivalent); this stays as the readable
    reference the engine is proven against.

    Guarantees sum_k party_mask(k) == 0 exactly (fp bit-exact / ring-exact).
    """
    dtype = mask_dtype(mode)
    total = jnp.zeros(() if scalar else shape, dtype)
    for j in range(n_passive):
        if j == k:
            continue
        m = pair_mask(seeds[(min(k, j), max(k, j))], shape, round_idx, mode,
                      scalar)
        total = total - m if k > j else total + m
    if scalar:
        total = jnp.broadcast_to(total, shape)
    if mode == "float" and scale != 1.0:
        # float-mask SNR control: unit-variance masks only partially hide
        # large-magnitude embeddings (measured in benchmarks/security_eval);
        # bigger masks hide better but cost fp32 cancellation precision —
        # the int32 ring mode avoids the trade-off entirely.
        total = total * scale
    return total


def all_party_masks(n_passive: int, seeds, shape, round_idx=0,
                    mode: str = "float", scalar: bool = False,
                    scale: float = 1.0) -> jnp.ndarray:
    """(K, *shape) stacked masks, one per passive party (loop oracle)."""
    return jnp.stack([
        party_mask(k, n_passive, seeds, shape, round_idx, mode, scalar,
                   scale)
        for k in range(n_passive)])


# ---------------------------------------------------------------------------
# vectorized mask engine (production path)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MaskEngine:
    """Batched pairwise-mask synthesis: O(1) traced XLA ops per round.

    ``seed_hi``/``seed_lo`` (K, K-1) uint32: row k holds the 63-bit pair
    seeds CK_{k,j} for j != k in ascending-j order, split into 32-bit words.
    ``signs`` (K, K-1) int32: the (-1)^{k>j} coefficient of Eq. 5.

    Host numpy arrays: the engine is closed over by jitted steps (the seed
    layout is a compile-time constant of the federation, fixed by the DH
    ceremony), never passed through a jit boundary as a traced argument.
    """
    n_passive: int
    seed_hi: np.ndarray
    seed_lo: np.ndarray
    signs: np.ndarray

    @classmethod
    def from_seeds(cls, n_passive: int,
                   seeds: Dict[Tuple[int, int], int]) -> "MaskEngine":
        K = n_passive
        hi = np.zeros((K, max(K - 1, 1)), np.uint32)
        lo = np.zeros((K, max(K - 1, 1)), np.uint32)
        sg = np.zeros((K, max(K - 1, 1)), np.int32)
        for k in range(K):
            col = 0
            for j in range(K):
                if j == k:
                    continue
                h, l = seed_words(seeds[(min(k, j), max(k, j))])
                hi[k, col], lo[k, col] = h, l
                sg[k, col] = 1 if k < j else -1
                col += 1
        return cls(n_passive=K, seed_hi=hi, seed_lo=lo, signs=sg)

    def masks(self, shape, round_idx=0, mode: str = "float",
              scalar: bool = False, scale: float = 1.0, *,
              mesh=None, party_axis: str = "party") -> jnp.ndarray:
        """(K, *shape) stacked masks — bit-exact vs ``all_party_masks``.

        One nested-vmap PRF generates every pair mask in a single traced
        op; the per-party combine is a ``lax.scan`` left-fold over the
        ascending-j pair axis, replaying the loop oracle's addition order
        (so float parity is bit-exact, not just cancellation-invariant;
        int32 parity is ring-exact by associativity).

        ``mesh``: per-group mask sharding (sharded party engine). When the
        K party rows divide the mesh's party axis, synthesis runs under
        ``shard_map`` so each device PRFs only its own parties' seed rows
        and the result is born sharded over the party axis — no mask
        tensor ever crosses devices. Rows are independent (the scan folds
        each party's own pair axis), so the sharded synthesis is
        bit-exact vs the replicated one.
        """
        K = self.n_passive
        shape = tuple(shape)
        dtype = mask_dtype(mode)
        mshape = () if scalar else shape

        def rows(hi_r, lo_r, sg_r, r_idx):
            def one(hi, lo):
                return _mask_from_words(hi, lo, r_idx, mshape, mode)

            pm = jax.vmap(jax.vmap(one))(hi_r, lo_r)
            coeff = sg_r.reshape(
                sg_r.shape[:2] + (1,) * len(mshape)).astype(dtype)
            terms = jnp.moveaxis(pm * coeff, 1, 0)    # (K-1, rows, *mshape)
            total, _ = jax.lax.scan(
                lambda c, t: (c + t, None),
                jnp.zeros((hi_r.shape[0],) + mshape, dtype), terms)
            return total

        hi = jnp.asarray(self.seed_hi)
        lo = jnp.asarray(self.seed_lo)
        sg = jnp.asarray(self.signs)
        r_idx = jnp.asarray(round_idx, jnp.int32)
        from repro import sharding as shard_rules
        if shard_rules.party_shardable(mesh, K, party_axis):
            from jax.sharding import PartitionSpec as P
            ax = P(party_axis)
            total = shard_rules.shard_map(
                rows, mesh, in_specs=(ax, ax, ax, P()),
                out_specs=ax)(hi, lo, sg, r_idx)
        else:
            total = rows(hi, lo, sg, r_idx)
        if scalar:
            total = total.reshape((K,) + (1,) * len(shape))
        if mode == "float" and scale != 1.0:
            total = total * scale
        if scalar:
            total = jnp.broadcast_to(total, (K,) + shape)
        return total


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class FusedMasks:
    """Marker standing in for a materialized (K, *shape) mask tensor.

    Carries only the round index; aggregation recognizes it and synthesizes
    the masks inside the fused Pallas kernel (or the MaskEngine fallback)
    instead of receiving them as an input tensor. Registered as a pytree so
    it can cross jit boundaries where a mask tensor normally would.
    """
    round_idx: jnp.ndarray

    def tree_flatten(self):
        return (self.round_idx,), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(round_idx=leaves[0])


# ---------------------------------------------------------------------------
# fixed-point quantization for the ring wire modes (beyond-paper)
# ---------------------------------------------------------------------------

FIXED_POINT_SCALE = 2 ** 16
# int8 dynamic scale: keep |sum_C round(x_i * scale)| <= 127 so the TRUE
# aggregate of C quantized embeddings fits one ring element and the
# mod-256 wrap of the masked sum recovers it exactly. Per-party rounding
# contributes <= 0.5 each, hence the 0.5*C headroom taken off 127.
INT8_LIMIT = 127.0


def quantize(x: jnp.ndarray, scale: int = FIXED_POINT_SCALE) -> jnp.ndarray:
    return jnp.round(x.astype(jnp.float32) * scale).astype(jnp.int32)


def ring_scale(amax, C: int, mode: str):
    """Quantization scale for a ring wire mode.

    ``int32``: the static 2^16 fixed-point scale (Z_2^32 has headroom for
    any realistic C at unit-magnitude embeddings). ``int8``: a per-round
    DYNAMIC scale derived from ``amax`` = max |E| over every party's
    (lane-zeroed) embedding, chosen so the true C-party sum of quantized
    values is <= 127 in magnitude: scale = (127 - 0.5*C) / (C * amax).
    The scalar is shipped alongside the uplink (one fp32 per leg — the
    documented magnitude leak of the int8 mode). ``stop_gradient`` on
    amax keeps the easter-mode surrogate gradient identical to float's.
    """
    if mode == "int32":
        return jnp.asarray(float(FIXED_POINT_SCALE), jnp.float32)
    assert mode == "int8", mode
    assert C < 2 * INT8_LIMIT, f"C={C} leaves no int8 headroom"
    amax = jax.lax.stop_gradient(jnp.asarray(amax, jnp.float32))
    return (INT8_LIMIT - 0.5 * C) / (C * jnp.maximum(amax, 1e-6))


def quantize_ring(x: jnp.ndarray, mode: str, scale=None) -> jnp.ndarray:
    """Quantize onto the mode's ring. int8 goes float -> int32 -> int8 so
    an out-of-range value WRAPS mod 256 (astype between ints truncates
    bits) instead of clamping — saturation would break the additive
    ring-cancellation algebra the masks rely on."""
    if mode == "int32":
        return quantize(x)
    assert mode == "int8", mode
    assert scale is not None, "int8 quantization needs the per-round scale"
    return (jnp.round(x.astype(jnp.float32) * scale)
            .astype(jnp.int32).astype(jnp.int8))


def blind_uplink(E: jnp.ndarray, masks, mask_mode: str,
                 scale=None) -> jnp.ndarray:
    """THE wire format of a passive party's uplink — the single place
    blinding is applied on every jnp engine path (train/serve/prefill;
    loop, vmap and in-shard all route here, directly or via
    ``aggregation.blind`` / ``aggregate_ring``): float mode ships
    E + r, int32 mode ships quantize(E) + r in Z_2^32, int8 mode ships
    quantize_ring(E, scale) + r in Z_2^8 (the add wraps mod 256 — XLA
    int8 addition is two's-complement), masks=None ships raw (the
    caller's explicit unblinded-oracle choice). The Pallas blind_agg
    kernels fuse their own mask-add and are parity-tested against this
    path.

    The float wire carries fp32 (``wire_leg_bytes`` counts 4 B/elt) for
    every embedding dtype: blinding a bf16 embedding in bf16 would round
    E + r to 8 mantissa bits, and the masks would then cancel in the
    aggregate only to bf16 rounding of |r|, not to fp32 rounding."""
    if masks is None:
        return E
    if mask_mode in RING_MODES:
        return quantize_ring(E, mask_mode, scale) + masks
    return E.astype(jnp.float32) + masks.astype(jnp.float32)


def dequantize(x: jnp.ndarray, scale=FIXED_POINT_SCALE) -> jnp.ndarray:
    """Inverse of ``quantize``/``quantize_ring`` (elementwise; any
    party-count averaging is the aggregator's job, see
    aggregation.aggregate_ring)."""
    return x.astype(jnp.float32) / scale


# ---------------------------------------------------------------------------
# wire byte accounting + int8 word packing
# ---------------------------------------------------------------------------


def wire_elt_bytes(mode: str) -> int:
    """Bytes per ring/float element on the wire (unpacked view)."""
    return 1 if mode == "int8" else 4


def wire_leg_bytes(n_elts: int, mode: str) -> int:
    """Bytes one leg (one party, one direction) ships for ``n_elts``
    payload elements: int8 packs 4 ring elements per int32 word (padded)
    plus the fp32 scale scalar; float/int32 ship 4-byte elements with no
    side scalar."""
    if mode == "int8":
        return 4 * ((n_elts + 3) // 4) + 4
    return 4 * n_elts


def pack_int8_words(x: np.ndarray) -> np.ndarray:
    """Flatten int8 ring elements into little-endian int32 wire words
    (4 elements per word, zero-padded) — the framing wire.py transmits
    and records in transcripts."""
    flat = np.ascontiguousarray(np.asarray(x, np.int8).reshape(-1))
    pad = (-flat.size) % 4
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, np.int8)])
    return flat.view("<i4")


def unpack_int8_words(words: np.ndarray, shape) -> np.ndarray:
    """Inverse of ``pack_int8_words`` for a known payload shape."""
    n = int(np.prod(shape)) if shape else 1
    flat = np.ascontiguousarray(np.asarray(words, "<i4")).view(np.int8)
    return flat[:n].reshape(shape)


def setup_passive_parties(n_passive: int, *, deterministic_seed: int | None
                          = None) -> Tuple[List[KeyPair], Dict]:
    """Full key ceremony for K passive parties. Returns (keys, pair seeds)."""
    keys = [keygen(_test_seed=(None if deterministic_seed is None
                               else deterministic_seed * 131 + k))
            for k in range(n_passive)]
    return keys, pairwise_seeds(keys)


def setup_mask_engine(n_passive: int, *, deterministic_seed: int | None
                      = None) -> MaskEngine:
    """Key ceremony + packed seed layout in one call."""
    _, seeds = setup_passive_parties(n_passive,
                                     deterministic_seed=deterministic_seed)
    return MaskEngine.from_seeds(n_passive, seeds)


# ---------------------------------------------------------------------------
# cached ceremony: one DH exchange per federation
#
# The ceremony costs K(K-1)/2 2048-bit modexps of host time. Train, serve
# and prefill step builders — and benchmark sweeps that instantiate one
# system per (C, engine) cell — all describe the SAME federation when the
# deterministic seed matches, so the ceremony (and the packed MaskEngine
# layout derived from it) is memoized on (K, seed). Only deterministic
# seeds are cacheable: a production ceremony (deterministic_seed=None)
# draws fresh secret keys and must never be reused across systems.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def cached_passive_setup(n_passive: int, deterministic_seed: int
                         ) -> Tuple[List[KeyPair], Dict]:
    """Memoized ``setup_passive_parties`` (deterministic seeds only)."""
    assert deterministic_seed is not None, \
        "production ceremonies (deterministic_seed=None) draw fresh " \
        "secret keys and must NOT be memoized — call " \
        "setup_passive_parties directly"
    return setup_passive_parties(n_passive,
                                 deterministic_seed=deterministic_seed)


@functools.lru_cache(maxsize=None)
def cached_mask_engine(n_passive: int, deterministic_seed: int) -> MaskEngine:
    """Memoized ceremony + packed (K, K-1) seed-word layout."""
    _, seeds = cached_passive_setup(n_passive, deterministic_seed)
    return MaskEngine.from_seeds(n_passive, seeds)
