"""EASTER training protocol (paper Alg. 1) — paper-scale instantiation.

One round (C = K+1 parties, party 0 = active):
  1. every party computes its local embedding E_k = h(theta_k, D_k);
     passive parties blind: [E_k] = E_k + r_k                      (lines 2-5)
  2. active aggregates the global embedding E = (1/C)(E_a + sum [E_k]) (l. 6)
  3. every party predicts R_k = p(theta_k, E)                      (lines 7-10)
  4. active computes L_k = LF(R_k, Y) and the loss signal for each
     party (label assist)                                          (lines 11-12)
  5. every party updates its own heterogeneous model with ITS OWN loss
     gradient: theta_k <- theta_k - eta * d L_k / d theta_k        (lines 13-15)

Gradient semantics (paper Alg. 1, line 14): party k updates with the gradient
of *its own* loss L_k only. For the embedding net this flows through the
global embedding's dependence on E_k alone — other parties' embeddings are
constants from party k's point of view. We implement this exactly with a
stop-gradient surrogate so that ONE ``jax.grad`` produces every party's
paper-faithful gradient:

    E_for_k = stop_grad(E) - stop_grad(E_k)/C + E_k/C      (value == E)

``grad_mode="joint"`` (beyond-paper) instead lets every loss reach every
embedding net (full cross-party gradient flow).

``assisted_grads`` is the message-passing reference implementation of the
paper's active-party-assisted backward pass (explicit vjp per party), used to
*prove* the surrogate matches the protocol (tests/test_protocol_grads.py).

Execution engines: ``engine="vectorized"`` (default) groups parties by
(arch, slice width) and runs each protocol step as one ``jax.vmap`` per
group (core/party_engine.py) — O(#groups) XLA ops, scales to C=128+.
``engine="sharded"`` additionally lays every group's stacked params and
feature slices out over a ``"party"`` mesh axis with ``shard_map``: the
training round blinds in-shard and the tiled all-gather of the blinded
uplink is the only party-axis collective (raw local embeddings never
leave their device). ``engine="loop"`` is the seed's per-party Python
loop, kept as the equivalence oracle (tests prove all three match).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import EasterConfig
from repro.core import aggregation, blinding, losses, party_models
from repro.core.party_engine import PartyEngine, StackedParties
from repro.core.party_models import PartyArch, decide_fn, embed_fn, init_party
from repro.optim import make_optimizer


@dataclass
class EasterClassifier:
    """Paper-scale EASTER system over vertically-split features."""
    easter: EasterConfig
    arches: List[PartyArch]             # C entries; [0] = active party
    n_features: List[int]               # per-party vertical feature split
    loss: str = "ce"
    grad_mode: str = "easter"           # easter (paper) | joint (beyond)
    # vectorized (grouped vmap) | sharded (grouped vmap laid out over a
    # "party" mesh axis with shard_map) | loop (seed oracle)
    engine: str = "vectorized"
    # party-axis mesh for engine="sharded"; None builds a 1-D mesh over
    # every local device (launch.mesh.make_party_mesh). A party group
    # that does not lay out over it (every group, on one device) runs
    # replicated: every device computes the whole group.
    mesh: Any = None
    use_kernel: bool = False            # fused Pallas blind_agg aggregation
    # synthesize masks inside the Pallas kernel (pltpu PRNG) instead of
    # materializing the (K, B, d) tensor: float mode only; off-TPU falls
    # back to the MaskEngine graph path (see aggregation).
    fused_masks: bool = False
    # beyond-paper ablation: C_VFL-style top-k sparsification of the
    # UPLINK embeddings (values+indices wire format), straight-through
    # gradients. 0 = off (paper). Composes with blinding: masks are
    # applied to the sparsified embedding.
    compress_frac: float = 0.0

    def __post_init__(self):
        assert len(self.arches) == len(self.n_features)
        assert self.engine in ("vectorized", "sharded", "loop"), self.engine
        self.C = len(self.arches)
        self.K = self.C - 1
        if self.engine == "sharded":
            if self.mesh is None:
                from repro.launch.mesh import make_party_mesh
                self.mesh = make_party_mesh()
            assert self.compress_frac == 0, \
                "top-k uplink compression needs the gathered raw stack — " \
                "not available under the sharded engine"
            assert not self.use_kernel and not self.fused_masks, \
                "the Pallas blind_agg kernel is single-device; use the " \
                "vectorized engine for kernel/fused-mask runs"
        self._eng = PartyEngine(
            self.arches, self.n_features,
            mesh=self.mesh if self.engine == "sharded" else None)
        if self.K > 1:
            # memoized DH ceremony: every system built from the same
            # deterministic seed describes the same federation, so serve /
            # train / benchmark builders share one set of modexps
            self.keys, self.seeds = blinding.cached_passive_setup(self.K, 7)
            self.mask_engine = blinding.cached_mask_engine(self.K, 7)
        else:
            self.keys, self.seeds = [], {}
            self.mask_engine = None
        if self.fused_masks:
            assert self.easter.mask_mode == "float", \
                "fused (in-kernel) mask synthesis is float-mode only"
            assert self.engine == "vectorized", \
                "fused mask synthesis requires the vectorized engine"
        assert self.easter.mask_mode in ("float",) + blinding.RING_MODES, \
            self.easter.mask_mode
        # ring masks are dense, so a top-k-sparsified uplink saves no wire
        # bytes in any ring mode (see bytes_per_round) — the combination
        # would pay sparsification accuracy loss for nothing; reject it
        assert not (self.compress_frac > 0
                    and self.easter.mask_mode in blinding.RING_MODES), \
            "compress_frac has no wire benefit under ring masking"

    # -- params ------------------------------------------------------------
    def init_params(self, key) -> List[dict]:
        ks = jax.random.split(key, self.C)
        return [init_party(ks[k], self.arches[k], self.n_features[k])
                for k in range(self.C)]

    # -- protocol steps ----------------------------------------------------
    def masks(self, batch: int, round_idx: int = 0):
        """Per-round masks: a (K, B, d) tensor (engine-synthesized or the
        loop oracle), or a FusedMasks marker when synthesis is deferred to
        the Pallas kernel."""
        if self.K < 2 or not self.easter.enabled:
            return None
        r = round_idx if self.easter.fresh_masks else 0
        if self.fused_masks:
            return blinding.FusedMasks(jnp.asarray(r, jnp.int32))
        shape = (batch, self.easter.d_embed)
        with obs.span("masks"):
            if self.engine in ("vectorized", "sharded"):
                return self.mask_engine.masks(shape, r,
                                              self.easter.mask_mode)
            return blinding.all_party_masks(self.K, self.seeds, shape, r,
                                            self.easter.mask_mode)

    def local_embeds(self, params, xs) -> jnp.ndarray:
        """(C, B, d_embed) local embeddings, party order."""
        if self.engine in ("vectorized", "sharded"):
            E_all = self._eng.embed_all(params, xs)
        else:
            E_all = jnp.stack([embed_fn(params[k], self.arches[k], xs[k])
                               for k in range(self.C)])
        if self.compress_frac > 0:
            from repro.core.baselines import _topk_sparsify
            # passive parties compress their uplink (active stays local)
            E_all = jnp.concatenate(
                [E_all[:1], _topk_sparsify(E_all[1:], self.compress_frac)], 0)
        return E_all

    def global_embed(self, E_all: jnp.ndarray, masks) -> jnp.ndarray:
        if isinstance(masks, blinding.FusedMasks):
            return aggregation.blind_and_aggregate_fused(
                E_all, self.mask_engine, masks.round_idx)
        if masks is not None and self.easter.mask_mode in blinding.RING_MODES:
            return aggregation.aggregate_ring(E_all, masks,
                                              self.easter.mask_mode)
        return aggregation.blind_and_aggregate(E_all, masks,
                                               use_kernel=self.use_kernel)

    def _per_party_E(self, E: jnp.ndarray, E_all) -> jnp.ndarray:
        """(C, B, d): the per-party view E_for_k of the global embedding."""
        if self.grad_mode == "easter" and E_all is not None:
            return (jax.lax.stop_gradient(E)[None]
                    - jax.lax.stop_gradient(E_all) / self.C
                    + E_all / self.C)
        return jnp.broadcast_to(E[None], (self.C,) + E.shape)

    def _predictions_stacked(self, params, E, E_all=None) -> jnp.ndarray:
        """(C, B, n_classes) logits, party order."""
        E_for = self._per_party_E(E, E_all)
        if self.engine in ("vectorized", "sharded"):
            return self._eng.decide_all(params, E_for)
        return jnp.stack([decide_fn(params[k], self.arches[k], E_for[k])
                          for k in range(self.C)])

    def predictions(self, params, E: jnp.ndarray, E_all=None) -> List:
        """R_k = p(theta_k, E_for_k) for every party (paper grad masking)."""
        R = self._predictions_stacked(params, E, E_all)
        return [R[k] for k in range(self.C)]

    def forward(self, params, xs, masks=None):
        E_all = self.local_embeds(params, xs)
        E = self.global_embed(E_all, masks)
        R = self.predictions(params, E, E_all)
        return E, R

    def loss_fn(self, params, xs, y, masks=None):
        """Total (sum over parties) + per-party losses."""
        if self.engine == "sharded":
            return self._loss_fn_sharded(params, xs, y, masks)
        E_all = self.local_embeds(params, xs)
        E = self.global_embed(E_all, masks)
        R_all = self._predictions_stacked(params, E, E_all)
        lf = losses.LOSSES[self.loss]
        per = jax.vmap(lambda r: lf(r, y))(R_all)
        return jnp.sum(per), per

    def _loss_fn_sharded(self, params, xs, y, masks=None):
        """Mesh-sharded training round. Party-axis wire, all of it
        protocol-legitimate: the tiled all-gather of the BLINDED passive
        uplink (active row zeroed — it sends nothing), one psum carrying
        the global embedding the active party aggregated locally (paper
        line 6 downlink), and the gathered predictions/losses. Raw local
        embeddings never leave their device: the stop-gradient surrogate
        is applied inside the decide shard. Bit-exact forward vs the
        vectorized engine (the aggregate replays ``blind_and_aggregate``'s
        op order on the gathered uplink)."""
        full_masks = None
        if masks is not None:
            assert not isinstance(masks, blinding.FusedMasks)
            full_masks = jnp.concatenate(
                [jnp.zeros((1,) + masks.shape[1:], masks.dtype), masks], 0)
        scale = None
        if full_masks is not None and self.easter.mask_mode == "int8":
            # int8 needs the per-round GLOBAL scale before anyone blinds:
            # stage 1 gathers per-party |E| maxima (scalars — the
            # documented int8 magnitude leak), stage 2 blinds in-shard
            # under the shared scale (see party_engine).
            E_parts, up, scale = self._eng.embed_blind_uplink_scaled(
                params, xs, full_masks, "int8")
        else:
            E_parts, up = self._eng.embed_blind_uplink(
                params, xs, full_masks, self.easter.mask_mode)
        if masks is None:
            E = jnp.mean(up, axis=0)
        elif self.easter.mask_mode == "int8":
            E = self._eng.aggregate_via_active(
                E_parts, up,
                lambda e_a, u: aggregation.aggregate_int8_blinded(
                    jnp.concatenate(
                        [blinding.quantize_ring(e_a, "int8", scale)[None],
                         u[1:]], 0), scale))
        elif self.easter.mask_mode == "int32":
            E = self._eng.aggregate_via_active(
                E_parts, up,
                lambda e_a, u: aggregation.aggregate_int32_blinded(
                    jnp.concatenate([blinding.quantize(e_a)[None], u[1:]],
                                    0)))
        else:
            E = self._eng.aggregate_via_active(
                E_parts, up,
                lambda e_a, u: aggregation.aggregate(e_a, u[1:]))
        C = self.C
        if self.grad_mode == "easter":
            def view(e_glob, e_loc):
                return (jax.lax.stop_gradient(e_glob)[None]
                        - jax.lax.stop_gradient(e_loc) / C + e_loc / C)
        else:
            def view(e_glob, e_loc):
                return jnp.broadcast_to(e_glob[None], e_loc.shape)
        R_all = self._eng.decide_from(params, E_parts, E, view)
        lf = losses.LOSSES[self.loss]
        per = jax.vmap(lambda r: lf(r, y))(R_all)
        return jnp.sum(per), per

    # -- assisted-gradient reference path (message passing) ----------------
    def assisted_grads(self, params, xs, y, masks=None):
        """Paper's explicit protocol: per-party vjp with active-party loss
        assist. Returns (grads list, per-party losses)."""
        if self.engine in ("vectorized", "sharded"):
            return self._assisted_grads_vectorized(params, xs, y, masks)
        lf = losses.LOSSES[self.loss]
        # step 1: local embeddings, keeping per-party vjp closures
        Es, vjp_embed = [], []
        for k in range(self.C):
            E_k, vjp_k = jax.vjp(
                lambda pk, k=k: embed_fn(pk, self.arches[k], xs[k]),
                params[k])
            Es.append(E_k)
            vjp_embed.append(vjp_k)
        E_all = jnp.stack(Es)
        # step 2: active party aggregates (masks cancel)
        E = self.global_embed(E_all, masks)
        E = jax.lax.stop_gradient(E)
        grads, per_losses = [], []
        for k in range(self.C):
            # step 3: party k predicts from the global embedding
            R_k, vjp_dec = jax.vjp(
                lambda pk, e, k=k: decide_fn(pk, self.arches[k], e),
                params[k], E)
            # step 4: ACTIVE party computes the loss signal dL_k/dR_k
            L_k, gR_k = jax.value_and_grad(lambda r: lf(r, y))(R_k)
            # step 5: party k backprops its decision net; receives dL_k/dE
            g_dec, gE = vjp_dec(gR_k)
            # step 6: embedding-net grad via dE/dE_k = 1/C (mean aggregation)
            (g_emb,) = vjp_embed[k](gE / self.C)
            g_k = jax.tree.map(lambda a, b: a + b, g_dec, g_emb)
            grads.append(g_k)
            per_losses.append(L_k)
        return grads, jnp.stack(per_losses)

    def _assisted_grads_vectorized(self, params, xs, y, masks=None):
        """Same message-passing semantics, one vjp per party *group*."""
        lf = losses.LOSSES[self.loss]
        # step 1: local embeddings with group-level pullbacks
        E_all, pull_embed = self._eng.embed_vjp(params, xs)
        # step 2: active party aggregates (masks cancel)
        E = jax.lax.stop_gradient(self.global_embed(E_all, masks))
        # step 3: every party predicts from the global embedding
        E_bcast = jnp.broadcast_to(E[None], (self.C,) + E.shape)
        R_all, pull_dec = self._eng.decide_vjp(params, E_bcast)
        # step 4: ACTIVE party computes every loss signal dL_k/dR_k at once
        L_all, gR_all = jax.vmap(
            jax.value_and_grad(lambda r: lf(r, y)))(R_all)
        # step 5: decision-net backprop; each party receives its dL_k/dE
        g_dec, gE_all = pull_dec(gR_all)
        # step 6: embedding-net grads via dE/dE_k = 1/C (mean aggregation)
        g_emb = pull_embed(gE_all / self.C)
        grads = [jax.tree.map(lambda a, b: a + b, g_dec[k], g_emb[k])
                 for k in range(self.C)]
        return grads, L_all

    # -- training ----------------------------------------------------------
    def make_train_step(self, optimizer_name: str, lr: float, *,
                        party_optimizers=None, **opt_kw):
        """(init_opt, jitted step) for one protocol round + update; the
        step is called through a ``train.step`` span (``repro.obs``) and
        keeps the jitted function as ``__wrapped__``.

        ``party_optimizers`` (paper §IV-E heterogeneous optimization):
        ``{party: (name, lr, hparams)}`` — parties not listed fall back
        to ``(optimizer_name, lr, opt_kw)``. Every party always updates
        with its OWN optimizer on its OWN loss gradient; the grouped
        engines stack states per (execution-group, optimizer) subgroup
        and vmap the update (``PartyEngine.update_groups``), so a
        homogeneous C=128 run pays O(#groups) update ops and a
        heterogeneous one O(#groups x #distinct optimizers) — the model
        stays vectorized either way. The loop engine keeps the
        per-party update loop as the oracle.

        On the grouped engines the step takes and returns params and
        state as ``StackedParties`` (one stack per such subgroup; on the
        sharded engine each stack laid out over the party axis), and
        ``init_opt`` returns its state so. A plain per-party list is
        stacked, by a jitted program, when the step is called with one:
        such a call counts once as ``train.restacks``. The loop engine
        takes and returns plain lists.
        """
        from repro.optim import resolve_party_optimizers
        default = (optimizer_name, lr, opt_kw)
        opts = resolve_party_optimizers(party_optimizers or {}, self.C,
                                        default=default)
        grouped = self.engine in ("vectorized", "sharded")

        def step(params, opt_state, xs, y, masks):
            (total, per), grads = jax.value_and_grad(
                self.loss_fn, has_aux=True)(params, xs, y, masks)
            if grouped:
                new_params, new_state = self._eng.update_groups(
                    opts, grads, opt_state, params)
            else:
                new_params, new_state = [], []
                for k in range(self.C):
                    p, s = opts[k].update(grads[k], opt_state[k], params[k])
                    new_params.append(p)
                    new_state.append(s)
            return new_params, new_state, total, per

        jitted = jax.jit(step)

        if grouped:
            eng, members = self._eng, self._eng.subgroups(opts)
            init = jax.jit(functools.partial(eng.init_groups, opts))
            stack = jax.jit(lambda t: StackedParties.stack(t, members))

            def init_opt(params):
                return eng.lay_out(init(params))

            def carry(parties):
                if isinstance(parties, StackedParties):
                    return parties
                return eng.lay_out(stack(parties))
        else:
            def init_opt(params):
                return [opts[k].init(p) for k, p in enumerate(params)]

        @functools.wraps(jitted)
        def traced_step(params, opt_state, xs, y, masks):
            with obs.span("train.step"):
                if grouped and not (isinstance(params, StackedParties) and
                                    isinstance(opt_state, StackedParties)):
                    obs.count("train.restacks")
                    params, opt_state = carry(params), carry(opt_state)
                return jitted(params, opt_state, xs, y, masks)

        return init_opt, traced_step

    def bytes_per_round(self, batch: int) -> int:
        """Wire bytes per training round (paper Table V accounting):
        blinded embeddings up + global embedding down + predictions up +
        loss signal down.

        Wire format depends on mask_mode — bytes/element derive from the
        wire dtype (``blinding.wire_leg_bytes``, satellite of the int8
        work: the accounting can no longer hard-code 4 B/elt). float mode
        ships fp32 payloads (4 B/elt) and composes with top-k compression
        (values + int32 indices). int32 ring mode ships Z_2^32 ring
        elements (4 B/elt). int8 ring mode ships Z_2^8 elements packed
        4-per-int32 word plus one fp32 scale scalar per leg, on ALL FOUR
        legs (the downlink is already grid-quantized, so re-shipping it
        as int8 words is exact; predictions/loss signals are
        point-to-point int8 under their own per-leg scale). Because ring
        masks are DENSE, top-k sparsification cannot shrink a ring-mode
        uplink (a sparse wire would reveal which coordinates were
        masked-only), so the compress_frac discount applies to float
        mode only.
        """
        d_e = self.easter.d_embed
        n_cls = self.arches[0].n_classes
        mode = self.easter.mask_mode
        up_e = self.K * blinding.wire_leg_bytes(batch * d_e, mode)
        if self.compress_frac > 0 and mode not in blinding.RING_MODES:
            # values + indices
            up_e = int(self.K * batch * d_e * 4 * self.compress_frac * 2)
        down_e = self.K * blinding.wire_leg_bytes(batch * d_e, mode)
        up_r = self.K * blinding.wire_leg_bytes(batch * n_cls, mode)
        down_l = self.K * blinding.wire_leg_bytes(batch * n_cls, mode)
        return up_e + down_e + up_r + down_l

    def accuracy(self, params, xs, y) -> jnp.ndarray:
        """Per-party test accuracy (the paper's theta_1..theta_C columns)."""
        E_all = self.local_embeds(params, xs)
        E = self.global_embed(E_all, None)
        R_all = self._predictions_stacked(params, E, E_all)
        return jnp.mean(jnp.argmax(R_all, -1) == y[None], axis=-1)


def split_features(x: jnp.ndarray, C: int) -> List[jnp.ndarray]:
    """Vertical split: feature dim into C near-equal slices (paper §V-A)."""
    F = x.shape[-1]
    sizes = [F // C + (1 if i < F % C else 0) for i in range(C)]
    out, off = [], 0
    for s in sizes:
        out.append(x[..., off:off + s])
        off += s
    return out
