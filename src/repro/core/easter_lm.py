"""EASTER at LLM scale — the production instantiation the dry-run lowers.

Parties:
  * party 0 (ACTIVE)  — the full assigned architecture as its backbone;
  * parties 1..K (PASSIVE) — heterogeneous reduced-depth proxies of the same
    family (depth x ``passive_depth_frac``), per the paper's heterogeneous
    setting (different local model sizes; cross-*family* heterogeneity is
    exercised at paper scale in core/protocol.py).

Per-party local model = backbone (hidden states) -> linear proj into the
shared embedding space R^{d_embed} (the paper's embedding layer h), then an
MLP decision stack + LM head (the paper's decision layers p; the paper's PL
is an MLP, so the LM-scale decision net is a per-position MLP stack).

The EASTER round is fused into one SPMD step:
  local embeds -> in-graph PRF blinding (passive) -> mean-aggregate ->
  per-party decision -> per-party loss (labels live with the active party) ->
  paper-faithful per-party gradients via the stop-gradient surrogate
  (see core/protocol.py docstring for the equivalence proof obligations).
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import sharding as shard_rules
from repro.configs.base import EasterConfig, ModelConfig
from repro.core import aggregation, blinding
from repro.core.losses import chunked_lm_head_xent, lm_xent
from repro.core.party_engine import stack_trees, unstack_tree
from repro.models import transformer
from repro.models.layers import (
    _dense_init, apply_norm, init_linear, init_mlp, init_norm, linear, mlp,
)


@functools.lru_cache(maxsize=None)
def _cached_mask_setup(num_passive: int, vectorized: bool):
    """One DH ceremony per (K, engine) — the EasterLM seed is fixed
    (deterministic_seed=1729), so the result is a pure function of K.
    Delegates to the blinding-level memoized ceremony so every step
    builder (train, serve, prefill) and every engine flavour shares the
    same K(K-1)/2 modexps."""
    if vectorized:
        return blinding.cached_mask_engine(num_passive, 1729)
    _, seeds = blinding.cached_passive_setup(num_passive, 1729)
    return seeds


def passive_cfg(cfg: ModelConfig, easter: EasterConfig, k: int) -> ModelConfig:
    """Heterogeneous passive-party proxy: reduced depth, same family.

    With ``easter.moe_dense_passive`` an MoE active gets DENSE passive
    proxies whose FFN width matches the MoE's *active* FLOPs
    (top_k x d_expert_ff) — same compute, zero expert all-to-all (§Perf H1).
    """
    frac = easter.passive_depth_frac
    n = max(2, int(round(cfg.n_layers * frac)))
    if cfg.family == "hybrid":
        n = max(len(cfg.hybrid.pattern), n - n % len(cfg.hybrid.pattern))
    kw = dict(n_layers=n, tie_embeddings=True,
              name=f"{cfg.name}-passive{k}")
    if cfg.family == "moe" and easter.moe_dense_passive:
        from repro.configs.base import MoEConfig
        kw.update(family="dense",
                  d_ff=cfg.moe.d_expert_ff
                  * (cfg.moe.top_k + cfg.moe.n_shared_experts),
                  moe=MoEConfig())
    return dataclasses.replace(cfg, **kw)


@dataclass(frozen=True)
class EasterLM:
    cfg: ModelConfig                 # active party's architecture
    easter: EasterConfig
    grad_mode: str = "easter"        # easter (paper) | joint (beyond-paper)
    # vectorized: the K passive proxies share one config (see passive_cfg),
    # so their params stack and the whole passive side runs under ONE
    # jax.vmap (core/party_engine.py idea at LLM scale) instead of a K-way
    # Python loop. sharded: the same stacked group additionally lays out
    # over a "party" mesh axis with shard_map — blinding happens in-shard
    # and the blinded uplink's all-gather is the only party-axis
    # collective. loop: the seed's per-party path (equivalence oracle).
    engine: str = "vectorized"
    # party-axis mesh for engine="sharded"; None = every local device.
    # When K is not a multiple of the axis the passive stack runs
    # replicated: every device computes all K parties (_shard_ok() says
    # which; the launchers refuse such a layout).
    mesh: Any = None

    @property
    def party_cfgs(self) -> List[ModelConfig]:
        active = dataclasses.replace(self.cfg, tie_embeddings=True)
        return [active] + [passive_cfg(self.cfg, self.easter, k)
                           for k in range(1, self.easter.num_passive + 1)]

    @property
    def C(self) -> int:
        return self.easter.num_passive + 1

    # -- blinding setup (host-side DH ceremony) -----------------------------
    def mask_seeds(self):
        """DH ceremony -> mask synthesis state. Returns a MaskEngine (the
        vectorized in-graph path, O(1) traced ops per round) or, for the
        loop oracle engine, the raw pair-seed dict. Cached: the train,
        serve, and prefill step builders all call this on the same system,
        and the ceremony costs K(K-1)/2 2048-bit modexps."""
        if self.easter.num_passive < 2 or not self.easter.enabled:
            return None
        return _cached_mask_setup(self.easter.num_passive,
                                  self.engine != "loop")

    # -- params --------------------------------------------------------------
    def init_party(self, key, pcfg: ModelConfig) -> Dict[str, Any]:
        kb, kp, kd, kh = jax.random.split(key, 4)
        d_e = self.easter.d_embed
        dtype = jnp.dtype(pcfg.dtype)
        decision = []
        for i in range(self.easter.decision_layers):
            ki = jax.random.fold_in(kd, i)
            decision.append({
                "ln": init_norm(pcfg.norm, d_e, dtype),
                "mlp": init_mlp(ki, d_e, 4 * d_e, pcfg.act, dtype)})
        return {
            "backbone": transformer.init_lm(kb, pcfg),
            "proj": init_linear(kp, pcfg.d_model, d_e, False, dtype),
            "decision": decision,
            "final_norm": init_norm(pcfg.norm, d_e, dtype),
            "head": init_linear(kh, d_e, pcfg.vocab_size, False, dtype),
        }

    def init_params(self, key) -> Dict[str, Any]:
        ks = jax.random.split(key, self.C)
        return {"parties": [self.init_party(ks[k], pcfg)
                            for k, pcfg in enumerate(self.party_cfgs)]}

    # -- protocol pieces -----------------------------------------------------
    def local_embed(self, pparams, pcfg: ModelConfig, tokens, *, caches=None,
                    pos_offset=0, window_override=-1, **fe):
        h, new_caches, aux = transformer.apply_lm(
            pparams["backbone"], tokens, pcfg, caches=caches,
            pos_offset=pos_offset, window_override=window_override,
            return_hidden=True, **fe)
        E = linear(pparams["proj"], h)                 # (B, S, d_embed)
        return E, new_caches, aux

    def masks_for(self, shape, round_idx, seeds, *, mesh=None):
        """seeds: None | MaskEngine | pair-seed dict (loop oracle).
        ``mesh``: per-group mask sharding — the MaskEngine synthesizes
        each device's party rows in-shard, so masks are born laid out
        over the party axis (sharded engine only).

        With ``fresh_masks=False`` and a TRACED round index, the static
        round is lowered as ``round_idx * barrier(0)`` — value 0 every
        round (the paper's single static pad), but opaque to XLA's
        constant folder. Lowering it as a literal 0 made the pads
        compile-time constants, and XLA folded them (and re-fused their
        consumers) DIFFERENTLY inside the fused decode scan
        (core/decode.py) than in a step-at-a-time jit — ~1e-6 float
        drift between two drivers of the SAME protocol. The traced zero
        keeps the PRF chain in the step body in both drivers, so they
        lower identically (bit-exactness pinned in
        tests/test_decode_scan.py) at the cost of re-synthesizing the
        static pad per round, which the default fresh-mask mode pays
        anyway."""
        if seeds is None:
            return None
        if self.easter.fresh_masks:
            r = round_idx
        elif isinstance(round_idx, jnp.ndarray):
            r = round_idx * jax.lax.optimization_barrier(
                jnp.zeros((), jnp.int32))
        else:
            r = 0
        if isinstance(seeds, blinding.MaskEngine):
            return seeds.masks(shape, r, self.easter.mask_mode, mesh=mesh)
        return blinding.all_party_masks(
            self.easter.num_passive, seeds, shape, r,
            self.easter.mask_mode)

    def decide_hidden(self, pparams, pcfg: ModelConfig, E):
        x = E
        for blk in pparams["decision"]:
            x = x + mlp(blk["mlp"], apply_norm(blk["ln"], x, pcfg.rms_eps),
                        pcfg.act)
        return apply_norm(pparams["final_norm"], x, pcfg.rms_eps)

    def decide(self, pparams, pcfg: ModelConfig, E):
        x = self.decide_hidden(pparams, pcfg, E)
        return linear(pparams["head"], x)              # (B, S, vocab)

    def _per_party_E(self, E, E_all, k):
        if self.grad_mode == "easter":
            return (jax.lax.stop_gradient(E)
                    - jax.lax.stop_gradient(E_all[k]) / self.C
                    + E_all[k] / self.C)
        return E

    def _passive_group_ok(self) -> bool:
        """True when parties 1..K are structurally identical (they are by
        construction of passive_cfg — only the name differs) and a
        stacked-group engine (vectorized or sharded) is selected."""
        if (self.engine not in ("vectorized", "sharded")
                or self.easter.num_passive < 1):
            return False
        anon = [dataclasses.replace(c, name="") for c in self.party_cfgs[1:]]
        return all(c == anon[0] for c in anon)

    @functools.cached_property
    def party_mesh(self):
        """Resolved party-axis mesh (engine="sharded" only) — cached so
        every shard_map/mask-synthesis site in a traced step sees the
        ONE Mesh object rather than re-building it per access."""
        if self.engine != "sharded":
            return None
        if self.mesh is not None:
            return self.mesh
        from repro.launch.mesh import make_party_mesh
        return make_party_mesh()

    def _shard_ok(self) -> bool:
        """True when the K-passive stack can lay out over the party axis."""
        return (self.engine == "sharded"
                and shard_rules.party_shardable(self.party_mesh,
                                                self.easter.num_passive))

    def _aggregate(self, E_all, round_idx, seeds, lane_mask=None):
        """Shared blind+aggregate step of both engines: sharding-constrained
        (C, B, S, d) -> constrained global E. Keep BOTH loss paths on this
        helper — they are each other's equivalence oracle.

        ``lane_mask`` (B,) bool — batched serving: rows of finished (EOS)
        request lanes are zeroed in BOTH the embeddings and the masks
        before blinding, so a frozen lane's uplink contribution is exactly
        0 on the wire (int32 included: quantize(0) == 0) and it leaks no
        further embedding material after its request completed."""
        from repro import sharding as shard_hints
        E_all = shard_hints.constrain(E_all, (None, "batch", None, None))
        masks = self.masks_for(E_all.shape[1:], round_idx, seeds)
        if masks is not None:
            masks = shard_hints.constrain(masks, (None, "batch", None, None))
        if lane_mask is not None:
            keep = lane_mask.reshape((1, -1) + (1,) * (E_all.ndim - 2))
            E_all = jnp.where(keep, E_all, 0)
            if masks is not None:
                masks = jnp.where(keep, masks, 0)
        if masks is not None and self.easter.mask_mode in blinding.RING_MODES:
            # int8 derives its per-round dynamic scale INSIDE aggregate_ring
            # from the lane-zeroed stack above, so frozen lanes influence
            # neither the scale nor the wire bytes
            E = aggregation.aggregate_ring(E_all, masks,
                                           self.easter.mask_mode)
        else:
            E = aggregation.blind_and_aggregate(E_all, masks)
        E = shard_hints.constrain(E, ("batch", None, None))
        return E_all, E

    # -- training forward/loss ----------------------------------------------
    def loss_fn(self, params, batch, round_idx, seeds):
        if self._passive_group_ok():
            return self._loss_fn_vectorized(params, batch, round_idx, seeds)
        tokens, labels = batch["tokens"], batch["labels"]
        fe = {k: v for k, v in batch.items() if k.endswith("_embed")}
        Es, auxes = [], []
        for k, pcfg in enumerate(self.party_cfgs):
            E_k, _, aux_k = self.local_embed(params["parties"][k], pcfg,
                                             tokens, **fe)
            Es.append(E_k)
            auxes.append(aux_k)
        E_all, E = self._aggregate(jnp.stack(Es), round_idx, seeds)
        per = []
        for k, pcfg in enumerate(self.party_cfgs):
            h_k = self.decide_hidden(params["parties"][k], pcfg,
                                     self._per_party_E(E.astype(E_all.dtype),
                                                       E_all, k))
            # fused head + CE: never materializes (B, S, V) logits
            per.append(chunked_lm_head_xent(
                h_k, params["parties"][k]["head"]["w"], labels))
        total = jnp.sum(jnp.stack(per)) + jnp.sum(jnp.stack(auxes))
        return total, jnp.stack(per)

    def _aggregate_grouped(self, E_a, up_p, blinded: bool, scale=None):
        """Aggregate the active embedding with the (gathered) passive
        uplink, replaying ``_aggregate``'s op order bit-for-bit. ``up_p``
        is already blinded when ``blinded`` (float: E+r; ring modes:
        quantize(E)+r), raw otherwise (seeds=None oracle). int8 needs the
        per-round ``scale`` the uplink was quantized under."""
        if not blinded:
            return jnp.mean(jnp.concatenate([E_a[None], up_p], axis=0), 0)
        if self.easter.mask_mode == "int8":
            return aggregation.aggregate_int8_blinded(
                jnp.concatenate(
                    [blinding.quantize_ring(E_a, "int8", scale)[None],
                     up_p], 0), scale)
        if self.easter.mask_mode == "int32":
            return aggregation.aggregate_int32_blinded(
                jnp.concatenate([blinding.quantize(E_a)[None], up_p], 0))
        return aggregation.aggregate(E_a, up_p)

    def _loss_fn_vectorized(self, params, batch, round_idx, seeds):
        """One vmap over the stacked passive group instead of a K-way loop.

        Grad semantics are identical to the loop path: the stop-gradient
        surrogate is applied to the stacked (C, B, S, d) per-party view, so
        ONE jax.grad still yields every party's own-loss-only gradient.
        """
        tokens, labels = batch["tokens"], batch["labels"]
        fe = {k: v for k, v in batch.items() if k.endswith("_embed")}
        pcfg_a, pcfg_p = self.party_cfgs[0], self.party_cfgs[1]
        E_a, _, aux_a = self.local_embed(params["parties"][0], pcfg_a,
                                         tokens, **fe)
        stacked = stack_trees(params["parties"][1:])
        if self._shard_ok():
            return self._loss_fn_sharded(params, batch, round_idx, seeds,
                                         E_a, aux_a, stacked)

        def embed_one(pp):
            E_k, _, aux_k = self.local_embed(pp, pcfg_p, tokens, **fe)
            return E_k, aux_k

        E_p, aux_p = jax.vmap(embed_one)(stacked)       # (K, B, S, d_e)
        E_all, E = self._aggregate(
            jnp.concatenate([E_a[None], E_p], axis=0), round_idx, seeds)
        E = E.astype(E_all.dtype)
        if self.grad_mode == "easter":
            E_for = (jax.lax.stop_gradient(E)[None]
                     - jax.lax.stop_gradient(E_all) / self.C
                     + E_all / self.C)                   # (C, B, S, d_e)
        else:
            E_for = jnp.broadcast_to(E[None], E_all.shape)
        h_a = self.decide_hidden(params["parties"][0], pcfg_a, E_for[0])
        per_a = chunked_lm_head_xent(
            h_a, params["parties"][0]["head"]["w"], labels)

        def decide_one(pp, e_k):
            h_k = self.decide_hidden(pp, pcfg_p, e_k)
            return chunked_lm_head_xent(h_k, pp["head"]["w"], labels)

        per_p = jax.vmap(decide_one)(stacked, E_for[1:])
        per = jnp.concatenate([per_a[None], per_p])
        total = jnp.sum(per) + aux_a + jnp.sum(aux_p)
        return total, per

    def _loss_fn_sharded(self, params, batch, round_idx, seeds,
                         E_a, aux_a, stacked):
        """Party-mesh training round at LLM scale.

        The K stacked passive proxies (and their freshly-synthesized
        masks, see ``MaskEngine.masks(mesh=...)``) lay out over the
        "party" axis; the stage-1 shard_map body embeds + blinds locally
        and the tiled all-gather of the blinded uplink is the only
        party-axis collective carrying embedding-shaped data (gathered
        per-party aux/losses are protocol wire the active party receives
        anyway). Forward is bit-exact vs the vectorized engine; grads
        agree to ~1 ulp (shard-local vjp fusion).
        """
        mesh, ax = self.party_mesh, shard_rules.PARTY_AXIS
        tokens, labels = batch["tokens"], batch["labels"]
        fe = {k: v for k, v in batch.items() if k.endswith("_embed")}
        pcfg_a, pcfg_p = self.party_cfgs[0], self.party_cfgs[1]
        C = self.C
        masks = self.masks_for(E_a.shape, round_idx, seeds, mesh=mesh)
        mask_mode = self.easter.mask_mode

        def embed_body(pp, tok, f, m=None):
            def one(p):
                E_k, _, aux_k = self.local_embed(p, pcfg_p, tok, **f)
                return E_k, aux_k

            E_k, aux_k = jax.vmap(one)(pp)
            up = blinding.blind_uplink(E_k, m, mask_mode)
            return (E_k, jax.lax.all_gather(aux_k, ax, axis=0, tiled=True),
                    jax.lax.all_gather(up, ax, axis=0, tiled=True))

        def embed_body8(pp, tok, f, m, amax_a):
            # int8-only twin of embed_body: every shard agrees on the
            # global amax (fp max is exact, so the pmax reproduces the
            # vectorized engine's max|E_all| bitwise) before quantizing
            # its own rows under the shared per-round scale.
            def one(p):
                E_k, _, aux_k = self.local_embed(p, pcfg_p, tok, **f)
                return E_k, aux_k

            E_k, aux_k = jax.vmap(one)(pp)
            amax = jnp.maximum(amax_a,
                               jax.lax.pmax(jnp.max(jnp.abs(E_k)), ax))
            scale = blinding.ring_scale(amax, C, "int8")
            up = blinding.blind_uplink(E_k, m, "int8", scale)
            return (E_k, jax.lax.all_gather(aux_k, ax, axis=0, tiled=True),
                    jax.lax.all_gather(up, ax, axis=0, tiled=True), scale)

        scale = None
        if masks is None:
            E_loc, aux_p, up_p = shard_rules.shard_map(
                embed_body, mesh, in_specs=(P(ax), P(), P()),
                out_specs=(P(ax), P(), P()))(stacked, tokens, fe)
        elif mask_mode == "int8":
            amax_a = jnp.max(jnp.abs(E_a))
            E_loc, aux_p, up_p, scale = shard_rules.shard_map(
                embed_body8, mesh,
                in_specs=(P(ax), P(), P(), P(ax), P()),
                out_specs=(P(ax), P(), P(), P()))(
                    stacked, tokens, fe, masks, amax_a)
        else:
            E_loc, aux_p, up_p = shard_rules.shard_map(
                embed_body, mesh, in_specs=(P(ax), P(), P(), P(ax)),
                out_specs=(P(ax), P(), P()))(stacked, tokens, fe, masks)

        E = self._aggregate_grouped(E_a, up_p, masks is not None, scale)
        E = E.astype(E_a.dtype)
        if self.grad_mode == "easter":
            E_for_a = (jax.lax.stop_gradient(E)
                       - jax.lax.stop_gradient(E_a) / C + E_a / C)
        else:
            E_for_a = E
        h_a = self.decide_hidden(params["parties"][0], pcfg_a, E_for_a)
        per_a = chunked_lm_head_xent(
            h_a, params["parties"][0]["head"]["w"], labels)

        grad_mode = self.grad_mode

        def decide_body(pp, e_loc, e_glob, lab):
            if grad_mode == "easter":
                e_for = (jax.lax.stop_gradient(e_glob)[None]
                         - jax.lax.stop_gradient(e_loc) / C + e_loc / C)
            else:
                e_for = jnp.broadcast_to(e_glob[None], e_loc.shape)

            def one(p, e):
                h_k = self.decide_hidden(p, pcfg_p, e)
                return chunked_lm_head_xent(h_k, p["head"]["w"], lab)

            per = jax.vmap(one)(pp, e_for)
            return jax.lax.all_gather(per, ax, axis=0, tiled=True)

        per_p = shard_rules.shard_map(
            decide_body, mesh, in_specs=(P(ax), P(ax), P(), P()),
            out_specs=P())(stacked, E_loc, E, labels)
        per = jnp.concatenate([per_a[None], per_p])
        total = jnp.sum(per) + aux_a + jnp.sum(aux_p)
        return total, per

    def train_chunk(self, params, opt_state, batches, step0, opt):
        """Fused multi-step training: N optimizer steps in ONE
        ``lax.scan`` — the training twin of ``serve_tokens`` (one trace,
        one compile, params + optimizer state device-resident as scan
        carry; see ``core/train_loop.py`` and
        ``train_loop.build_train_chunk`` for the jitted, state-donating
        form). The scan body is the ordinary train step built on
        ``loss_fn``, so engines, mask modes and the TRAIN-domain
        per-step round schedule (``step0 + i``) are inherited verbatim
        and proven bit-exact against the step-at-a-time jitted loop.
        ``opt`` is any Optimizer-shaped object, including the paper's
        §IV-E heterogeneous ``optim.make_party_optimizers``."""
        from repro.core import train_loop
        return train_loop.train_chunk(
            train_loop.make_train_step(self, opt),
            params, opt_state, batches, step0)

    # -- serving -------------------------------------------------------------
    def init_caches(self, batch: int, cache_len: int,
                    window_override: int = -1, per_lane: bool = False):
        """KV caches for every party. ``per_lane=True`` gives each batch
        row its own position counter (continuous-batching decode slots —
        required whenever ``serve_step`` is driven with a vector pos)."""
        return [transformer.init_cache(pcfg, batch, cache_len,
                                       window_override, per_lane)
                for pcfg in self.party_cfgs]

    def serve_tokens(self, params, tokens, caches, pos, n_steps: int,
                     seeds, *, key=None, temperature: float = 0.0,
                     window_override: int = -1, fe_list=None,
                     return_logits: bool = False):
        """Fused multi-token decode: ``n_steps`` serve rounds in ONE
        ``lax.scan`` — the production generation path (one trace, one
        compile, caches device-resident as scan carry; see
        ``core/decode.py`` and ``decode.build_serve_tokens`` for the
        jitted, cache-donating form). The scan body is ``serve_step``
        itself, so engines and per-step blinding semantics are inherited
        verbatim and proven bit-exact against the step-at-a-time loop.

        DEPRECATED: new callers should use the typed serving surface —
        ``core.api.build_decoder`` (ServeRequest/DecodeState) — which
        adds request batching and EOS early-exit. This shim keeps the
        legacy single-stream signature for one release."""
        from repro.core import decode
        return decode.serve_tokens(
            self, params, tokens, caches, pos, n_steps, seeds, key=key,
            temperature=temperature, window_override=window_override,
            fe_list=fe_list, return_logits=return_logits)

    def serve_step(self, params, tokens, caches, pos, seeds,
                   window_override: int = -1, fe_list=None, *,
                   lane_mask=None, nonces=None):
        """One decode step: tokens (B,1). Returns (active logits, caches).

        Production generation drives N of these inside a single
        ``lax.scan`` via ``serve_tokens`` / ``core/decode.py`` — prefer
        that path (step-at-a-time jit dispatch re-enters every passive KV
        cache through the jit boundary per token). This single-step form
        is the oracle the fused scan is proven bit-exact against.

        The decode uplink is blinded through the SAME _aggregate plumbing
        as training — the paper's trust model (§IV-B/C) holds at inference
        too: int32 mode routes through aggregate_int32 (a previous version
        silently served UNBLINDED passive embeddings in that mode), and
        SERVE_DOMAIN + ``pos`` acts as the round index so that, with
        fresh_masks (the default), decode masks are fresh per step and
        never collide with a training round's (fresh_masks=False is the
        paper-literal static-pad mode: reuse is its documented semantics).

        fe_list: per-party frontend extras (e.g. whisper's precomputed
        cross-attention ``enc_kv``) — party models are heterogeneous, so
        these differ per party.

        Execution engines mirror training: with a stackable passive group
        the K proxies decode under one vmap (engine="vectorized") or
        K-parallel across the party mesh with in-shard blinding
        (engine="sharded"); the loop path remains the per-party oracle.

        Batched serving (core/serving.py) extends the step with per-LANE
        state: ``pos`` may be an (B,) vector (each request lane at its own
        sequence position — caches must then be per-lane,
        ``init_caches(per_lane=True)``); ``nonces`` (B,) switches the PRF
        round to the per-lane ``blinding.serve_round(nonce, pos)`` schedule
        so concurrent lanes never share a pad; ``lane_mask`` (B,) zeroes
        finished lanes' uplink contributions (see ``_aggregate``).
        """
        round_idx = (blinding.SERVE_DOMAIN + pos if nonces is None
                     else blinding.serve_round(nonces, pos))
        po = pos[:, None] if jnp.ndim(pos) == 1 else pos
        if self._passive_group_ok():
            return self._serve_step_grouped(params, tokens, caches, po,
                                            seeds, window_override, fe_list,
                                            round_idx, lane_mask)
        Es, new_caches = [], []
        for k, pcfg in enumerate(self.party_cfgs):
            fe = fe_list[k] if fe_list else {}
            E_k, nc, _ = self.local_embed(
                params["parties"][k], pcfg, tokens, caches=caches[k],
                pos_offset=po, window_override=window_override, **fe)
            Es.append(E_k)
            new_caches.append(nc)
        E_all, E = self._aggregate(jnp.stack(Es), round_idx, seeds,
                                   lane_mask)
        logits = self.decide(params["parties"][0], self.party_cfgs[0],
                             E.astype(E_all.dtype))
        return logits, new_caches

    def _passive_embed_grouped(self, params, tokens, caches, pos,
                               window_override, fe_list, round_idx, seeds,
                               lane_mask=None, amax_a=None):
        """Shared passive-side embed of the grouped serve/prefill paths.

        Stacks the K passive params/caches/frontend-extras and runs ONE
        vmapped ``local_embed`` — under ``engine="sharded"`` the stack
        (and the per-request masks) lays out over the party mesh and the
        blinded uplink is gathered in-shard, mirroring training.

        Returns ``(up_p, new_caches_p, blinded, scale)``: the (K, B, S, d)
        passive uplink as the active party observes it (blinded when
        ``seeds`` is set), the stacked new passive caches, whether
        blinding was applied, and — int8 sharded only — the per-round
        dynamic scale agreed in-shard (``amax_a`` is the active party's
        lane-zeroed max|E_a|, folded into the pmax so the scale matches
        the vectorized engine's max|E_all| bitwise).
        """
        pcfg_p = self.party_cfgs[1]
        wo = window_override
        sp = stack_trees(params["parties"][1:])
        sc = stack_trees(caches[1:])
        sfe = stack_trees(fe_list[1:]) if fe_list else {}

        def embed_k(pp, cc, f, tok, pos_):
            def one(p, c, ff):
                E_k, nc, _ = self.local_embed(p, pcfg_p, tok, caches=c,
                                              pos_offset=pos_,
                                              window_override=wo, **ff)
                return E_k, nc

            return jax.vmap(one)(pp, cc, f)

        if not self._shard_ok():
            E_p, nc_p = embed_k(sp, sc, sfe, tokens, pos)
            return E_p, nc_p, None, None  # caller blinds via _aggregate
        mesh, ax = self.party_mesh, shard_rules.PARTY_AXIS
        # (B, S, d) per-party embedding shape this step produces
        eshape = (tokens.shape[0], tokens.shape[1], self.easter.d_embed)
        masks = self.masks_for(eshape, round_idx, seeds, mesh=mesh)
        mask_mode = self.easter.mask_mode
        want_scale = masks is not None and mask_mode == "int8"
        C = self.C

        def body(pp, cc, f, tok, pos_, *rest):
            rest = list(rest)
            m = rest.pop(0) if masks is not None else None
            keep = rest.pop(0) if lane_mask is not None else None
            amax_in = rest.pop(0) if want_scale else None
            E_k, nc = embed_k(pp, cc, f, tok, pos_)
            scale = None
            if amax_in is not None:
                # amax over LANE-ZEROED embeddings: frozen lanes must not
                # move the scale (the vmap path zeroes E_all before its
                # max), and every shard pmax-agrees on the same scalar
                E_z = E_k
                if keep is not None:
                    kz = keep.reshape((1, -1) + (1,) * (E_k.ndim - 2))
                    E_z = jnp.where(kz, E_k, 0)
                amax = jnp.maximum(amax_in, jax.lax.pmax(
                    jnp.max(jnp.abs(E_z)), ax))
                scale = blinding.ring_scale(amax, C, "int8")
            up = blinding.blind_uplink(E_k, m, mask_mode, scale)
            if keep is not None:
                # frozen request lanes ship an exactly-zero uplink
                # (mirrors _aggregate's lane zeroing on the vmap path)
                kb = keep.reshape((1, -1) + (1,) * (up.ndim - 2))
                up = jnp.where(kb, up, 0)
            outs = (jax.lax.all_gather(up, ax, axis=0, tiled=True), nc)
            return outs + ((scale,) if want_scale else ())

        # params / caches / frontend-extras all carry the stacked K axis
        specs = [P(ax), P(ax), P(ax), P(), P()]
        args = [sp, sc, sfe, tokens, pos]
        if masks is not None:
            specs.append(P(ax))
            args.append(masks)
        if lane_mask is not None:
            specs.append(P())
            args.append(lane_mask)
        if want_scale:
            specs.append(P())
            args.append(jnp.asarray(0.0 if amax_a is None else amax_a,
                                    jnp.float32))
        out_specs = (P(), P(ax)) + ((P(),) if want_scale else ())
        res = shard_rules.shard_map(
            body, mesh, in_specs=tuple(specs),
            out_specs=out_specs)(*args)
        scale = res[2] if want_scale else None
        return res[0], res[1], masks is not None, scale

    def _serve_step_grouped(self, params, tokens, caches, pos, seeds,
                            window_override, fe_list, round_idx,
                            lane_mask=None):
        pcfg_a = self.party_cfgs[0]
        fe_a = fe_list[0] if fe_list else {}
        E_a, nc_a, _ = self.local_embed(
            params["parties"][0], pcfg_a, tokens, caches=caches[0],
            pos_offset=pos, window_override=window_override, **fe_a)
        amax_a = None
        if (self.easter.mask_mode == "int8" and seeds is not None
                and self._shard_ok()):
            # int8 sharded: the active party's lane-zeroed amax feeds the
            # in-shard scale agreement (hoisted before the passive call)
            E_a_z = E_a
            if lane_mask is not None:
                ka = lane_mask.reshape((-1,) + (1,) * (E_a.ndim - 1))
                E_a_z = jnp.where(ka, E_a, 0)
            amax_a = jnp.max(jnp.abs(E_a_z))
        up_p, nc_p, blinded, scale = self._passive_embed_grouped(
            params, tokens, caches, pos, window_override, fe_list,
            round_idx, seeds, lane_mask, amax_a)
        if blinded is None:              # vectorized: blind in _aggregate
            E_all, E = self._aggregate(
                jnp.concatenate([E_a[None], up_p], axis=0),
                round_idx, seeds, lane_mask)
            E = E.astype(E_all.dtype)
        else:                            # sharded: uplink already blinded
            if lane_mask is not None:
                # match _aggregate's lane zeroing so both engines compute
                # the identical (zero) aggregate row for frozen lanes
                ka = lane_mask.reshape((-1,) + (1,) * (E_a.ndim - 1))
                E_a = jnp.where(ka, E_a, 0)
            E = self._aggregate_grouped(E_a, up_p, blinded,
                                        scale).astype(E_a.dtype)
        logits = self.decide(params["parties"][0], pcfg_a, E)
        new_caches = [nc_a] + unstack_tree(nc_p, self.easter.num_passive)
        return logits, new_caches

    def prefill(self, params, tokens, caches, window_override: int = -1,
                fe_list=None, seeds=None, round_idx=0):
        """Cache-building forward over the prompt; returns (E, caches).

        The returned caches are the scan carry ``serve_tokens`` (the fused
        production decode, core/decode.py) starts from — hand them
        straight to ``decode.build_serve_tokens``'s jitted fn, which
        donates them so the whole generation stays device-resident.

        The prompt-phase uplink crosses the same trust boundary as every
        other round, so it is blinded through _aggregate like training and
        decode (a previous version aggregated RAW passive embeddings with
        a bare jnp.mean). ``seeds=None`` keeps the unblinded oracle used by
        parity tests.

        ``round_idx`` is a per-REQUEST nonce: with fresh_masks (the
        default), two prefills blinded under the same round reuse the
        pairwise one-time pads, letting the active party subtract the
        blinded uplinks and recover exact embedding differences — serving
        callers must supply a fresh nonce per request (see
        launch/steps.build_prefill_step). Internally offset by
        PREFILL_DOMAIN so prompt masks never coincide with training-round
        or decode-step masks (fresh_masks=False deliberately collapses
        all of this to the paper's single static pad)."""
        if self._passive_group_ok():
            return self._prefill_grouped(params, tokens, caches,
                                         window_override, fe_list, seeds,
                                         round_idx)
        Es, new_caches = [], []
        for k, pcfg in enumerate(self.party_cfgs):
            fe = fe_list[k] if fe_list else {}
            E_k, nc, _ = self.local_embed(
                params["parties"][k], pcfg, tokens, caches=caches[k],
                window_override=window_override, **fe)
            Es.append(E_k)
            new_caches.append(nc)
        _, E = self._aggregate(jnp.stack(Es),
                               blinding.PREFILL_DOMAIN + round_idx, seeds)
        return E, new_caches

    def _prefill_grouped(self, params, tokens, caches, window_override,
                         fe_list, seeds, round_idx):
        pcfg_a = self.party_cfgs[0]
        fe_a = fe_list[0] if fe_list else {}
        E_a, nc_a, _ = self.local_embed(
            params["parties"][0], pcfg_a, tokens, caches=caches[0],
            window_override=window_override, **fe_a)
        amax_a = None
        if (self.easter.mask_mode == "int8" and seeds is not None
                and self._shard_ok()):
            amax_a = jnp.max(jnp.abs(E_a))
        up_p, nc_p, blinded, scale = self._passive_embed_grouped(
            params, tokens, caches, 0, window_override, fe_list,
            blinding.PREFILL_DOMAIN + round_idx, seeds, amax_a=amax_a)
        if blinded is None:              # vectorized: blind in _aggregate
            _, E = self._aggregate(
                jnp.concatenate([E_a[None], up_p], axis=0),
                blinding.PREFILL_DOMAIN + round_idx, seeds)
        else:                            # sharded: uplink already blinded
            E = self._aggregate_grouped(E_a, up_p, blinded, scale)
        new_caches = [nc_a] + unstack_tree(nc_p, self.easter.num_passive)
        return E, new_caches

    def encoder_kv(self, params, audio_embed):
        """Whisper path: per-party precomputed cross-attention K/V.

        With a stackable passive group the K proxy encoders run under one
        vmap instead of a per-party loop (they share a config, so their
        K/V shapes match). The returned ``fe_list`` is computed ONCE per
        request and closed over by the fused decode scan
        (``serve_tokens``'s ``fe_list=``) — it is read-only per step, so
        it rides as a scan constant, not carry."""

        def one_kv(bp, pcfg):
            enc_out = transformer.encode(bp, audio_embed, pcfg)
            return transformer._encoder_kv(bp, enc_out, pcfg)

        if not self._passive_group_ok():
            return [{"enc_kv": one_kv(params["parties"][k]["backbone"], pcfg)}
                    for k, pcfg in enumerate(self.party_cfgs)]
        active = {"enc_kv": one_kv(params["parties"][0]["backbone"],
                                   self.party_cfgs[0])}
        pcfg_p = self.party_cfgs[1]
        stacked = stack_trees([p["backbone"] for p in params["parties"][1:]])
        kvs = jax.vmap(lambda bp: one_kv(bp, pcfg_p))(stacked)
        return [active] + [{"enc_kv": t}
                           for t in unstack_tree(kvs,
                                                 self.easter.num_passive)]
