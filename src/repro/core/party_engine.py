"""Vectorized + mesh-sharded many-party execution engine.

The paper runs C = 4 parties, and the seed implementation looped over them
in Python (`for k in range(C)`), which builds C separate XLA subgraphs and
caps the reproduction at a handful of participants. This module groups
parties by *execution signature* — ``(PartyArch, n_features)``; parties with
the same signature have identical param pytree shapes — stacks each group's
params along a leading axis, and runs embed/decide/vjp steps with one
``jax.vmap`` per group. With C=128 near-equal vertical slices there are at
most ``2 x len(distinct arches)`` groups (slice widths differ by at most 1),
so the protocol round is O(#groups) XLA ops instead of O(C).

Party order is preserved end-to-end: group outputs are concatenated and
re-scattered through a precomputed permutation so ``(C, B, ...)`` results
are bit-identical in layout to the loop engine's ``jnp.stack`` of per-party
results.

Parameters and optimizer state are carried as ``StackedParties``: one
stack per (execution-group, optimizer) subgroup, the subgroups
``update_groups`` forms. That is what crosses a jitted train step's
boundary, so a call hands over O(#groups) arrays instead of O(C) per-party
leaves, and the engine takes the stacks as they are. Each party still owns
its own rows: ``StackedParties`` is a ``Sequence`` whose ``x[k]`` slices
party k's pytree out of its stack. Every method also accepts a plain
per-party list (the loop oracle, tests) and stacks it. Per-party views are
still produced by indexing a ``StackedParties`` and by the pullbacks of
``embed_vjp`` / ``decide_vjp``, whose grads come back as per-party lists.

Mesh mode (``mesh=`` + ``party_axis=``): the protocol is embarrassingly
parallel across participants, so each group's stacked params and feature
slices additionally lay out over a ``"party"`` mesh axis with ``shard_map``
(``repro.sharding.shard_map``) and the group vmap runs K-parallel
across devices. Two execution families:

  * raw steps (``embed_all`` / ``decide_all`` / ``embed_vjp`` /
    ``decide_vjp``) — compute shards over the party axis, outputs are
    all-gathered back to every device (API-compatible with the
    single-device engine; used by the assisted-grad reference oracle and
    the accuracy/forward paths).
  * the blinded production round (``embed_blind_uplink`` +
    ``aggregate_via_active`` + ``decide_from``) — local embeddings NEVER
    leave their device raw: the stage-1 body blinds in-shard
    ([E_k] = E_k + r_k, or the Z_2^32 quantize-add in int32 mode) and
    zeroes the active party's row (it sends nothing on the uplink), the
    tiled all-gather of that blinded uplink is the embedding-shaped
    party collective, the active party's device aggregates locally and a
    psum broadcasts the global embedding (paper line 6 downlink), and
    stage 2 maps it back through a caller-supplied per-party view (the
    stop-gradient surrogate) against the still-sharded local embeddings.

A group whose size is not a multiple of the party axis runs the plain vmap
path replicated: every device computes the whole group, so nothing is
laid out or parallel (entry points that promise a sharded run refuse
such a layout: ``launch.mesh.require_party_layout``). Forward values are
bit-exact vs the single-device engine; backward passes agree to ~1 ulp
(XLA fuses the shard-local vjp bodies differently — proven tight in
tests/test_party_sharding.py).

Used by ``core/protocol.py`` (paper scale) and ``core/easter_lm.py`` (LLM
scale, where the K passive proxies share one config and form one group).
Equivalence with the loop engine is proven in tests/test_protocol_grads.py.
"""
from __future__ import annotations

from collections.abc import Sequence as SequenceABC
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding as shard_rules
from repro.core import blinding
from repro.core.party_models import PartyArch, decide_fn, embed_fn


def group_by(keys: Sequence[Any]) -> List[Tuple[Any, Tuple[int, ...]]]:
    """Stable grouping: (key, member indices) in first-seen key order."""
    groups: Dict[Any, List[int]] = {}
    for i, k in enumerate(keys):
        groups.setdefault(k, []).append(i)
    return [(k, tuple(v)) for k, v in groups.items()]


def stack_trees(trees: Sequence[Any]):
    """Stack a list of identically-shaped pytrees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def unstack_tree(tree, n: int) -> List[Any]:
    """Inverse of stack_trees: split the leading axis back into a list."""
    return [jax.tree.map(lambda x: x[i], tree) for i in range(n)]


@jax.tree_util.register_pytree_node_class
class StackedParties(SequenceABC):
    """C per-party pytrees carried as one stack per party subgroup.

    ``stacks[g]`` is the pytree of parties ``members[g]`` stacked along a
    leading axis, in that order; the members partition ``range(C)``. As a
    pytree its children are the stacks and ``members`` is its aux data, so
    a jitted call takes one array per stacked leaf. As a ``Sequence``,
    ``x[k]`` is party k's pytree sliced from its stack, with the structure
    and values a plain per-party list would hold.
    """
    __slots__ = ("stacks", "members", "_where")

    def __init__(self, stacks, members: Tuple[Tuple[int, ...], ...]):
        self.stacks = tuple(stacks)
        self.members = members
        self._where = None

    @classmethod
    def stack(cls, parties: Sequence[Any],
              members: Tuple[Tuple[int, ...], ...]) -> "StackedParties":
        return cls([_stack(parties, m) for m in members], members)

    def tree_flatten(self):
        return self.stacks, self.members

    @classmethod
    def tree_unflatten(cls, members, stacks):
        return cls(stacks, members)

    def where(self, k: int) -> Tuple[int, int]:
        """(subgroup, row) of party k."""
        if self._where is None:
            self._where = {i: (g, j) for g, m in enumerate(self.members)
                           for j, i in enumerate(m)}
        return self._where[k]

    def take(self, idx: Tuple[int, ...]):
        """Parties ``idx`` stacked along a leading axis, in that order: a
        subgroup's own stack as it is, else gathered from the stacks."""
        if idx in self.members:
            return self.stacks[self.members.index(idx)]
        where = [self.where(i) for i in idx]
        offset: Dict[int, int] = {}
        for g, _ in where:
            offset.setdefault(g, sum(len(self.members[h]) for h in offset))
        rows = jnp.asarray([offset[g] + j for g, j in where], jnp.int32)
        return jax.tree.map(lambda *xs: jnp.concatenate(xs)[rows],
                            *[self.stacks[g] for g in offset])

    def __len__(self) -> int:
        return sum(len(m) for m in self.members)

    def __getitem__(self, k: int):
        C = len(self)
        if not -C <= k < C:
            raise IndexError(k)
        g, j = self.where(k % C)
        return _row(self.stacks[g], j)


@jax.jit
def _row(stack, j):
    """Row ``j`` of every leaf: one call a party, whatever its leaves."""
    return jax.tree.map(lambda x: x[j], stack)


def _stack(parties: Sequence[Any], idx: Tuple[int, ...]):
    """Parties ``idx`` of ``parties`` stacked along a leading axis."""
    if isinstance(parties, StackedParties):
        return parties.take(idx)
    return stack_trees([parties[i] for i in idx])


class PartyEngine:
    """Grouped-vmap executor for C heterogeneous paper-scale parties."""

    def __init__(self, arches: Sequence[PartyArch],
                 n_features: Sequence[int], mesh=None,
                 party_axis: str = shard_rules.PARTY_AXIS):
        assert len(arches) == len(n_features)
        self.C = len(arches)
        self.arches = list(arches)
        self.n_features = list(n_features)
        assert len({a.d_embed for a in arches}) == 1, "d_embed must be shared"
        assert len({a.n_classes for a in arches}) == 1, "labels are shared"
        self.mesh = mesh
        self.party_axis = party_axis
        self.groups = group_by(list(zip(self.arches, self.n_features)))
        order = [i for _, idx in self.groups for i in idx]
        inv = [0] * self.C
        for pos, i in enumerate(order):
            inv[i] = pos
        # concat-of-groups index for party i (host-side constant)
        self._perm = jnp.asarray(inv, jnp.int32)

    @property
    def n_groups(self) -> int:
        return len(self.groups)

    # -- helpers -----------------------------------------------------------
    def _scatter(self, group_outs: List[jnp.ndarray]) -> jnp.ndarray:
        """Concat per-group (G_i, B, ...) results -> (C, B, ...) party order."""
        return jnp.concatenate(group_outs, axis=0)[self._perm]

    def _gather(self, x_per_party: jnp.ndarray, idx) -> jnp.ndarray:
        """(C, B, ...) -> this group's (G, B, ...) slab."""
        return x_per_party[jnp.asarray(idx, jnp.int32)]

    def _sharded(self, n_group: int) -> bool:
        return shard_rules.party_shardable(self.mesh, n_group,
                                           self.party_axis)

    def _gathered(self, fn: Callable, n_in: int) -> Callable:
        """shard_map ``fn`` over the party axis, all-gathering its single
        output back to replicated — the drop-in sharded twin of a stacked
        group fn (raw path: outputs DO cross the party collective)."""
        ax = self.party_axis

        def body(*args):
            return jax.lax.all_gather(fn(*args), ax, axis=0, tiled=True)

        return shard_rules.shard_map(
            body, self.mesh, in_specs=(P(ax),) * n_in, out_specs=P())

    # -- forward -----------------------------------------------------------
    def embed_all(self, params: Sequence[dict], xs: Sequence[jnp.ndarray]
                  ) -> jnp.ndarray:
        """E_k = h(theta_k, D_k) for all parties -> (C, B, d_embed)."""
        outs = []
        for (arch, _), idx in self.groups:
            sp = _stack(params, idx)
            sx = jnp.stack([xs[i] for i in idx])

            def gf(p, x, a=arch):
                return jax.vmap(lambda pi, xi: embed_fn(pi, a, xi))(p, x)

            if self._sharded(len(idx)):
                gf = self._gathered(gf, 2)
            outs.append(gf(sp, sx))
        return self._scatter(outs)

    def decide_all(self, params: Sequence[dict], E_per_party: jnp.ndarray
                   ) -> jnp.ndarray:
        """R_k = p(theta_k, E_for_k): (C, B, d) -> (C, B, n_classes)."""
        outs = []
        for (arch, _), idx in self.groups:
            sp = _stack(params, idx)
            se = self._gather(E_per_party, idx)

            def gf(p, e, a=arch):
                return jax.vmap(lambda pi, ei: decide_fn(pi, a, ei))(p, e)

            if self._sharded(len(idx)):
                gf = self._gathered(gf, 2)
            outs.append(gf(sp, se))
        return self._scatter(outs)

    # -- blinded production round (sharded path) ---------------------------
    def embed_blind_uplink(self, params: Sequence[dict],
                           xs: Sequence[jnp.ndarray],
                           full_masks: Optional[jnp.ndarray],
                           mask_mode: str = "float"):
        """Stage 1 of the sharded protocol round: embed + blind in-shard.

        ``full_masks`` (C, *mask_shape), party order, zero row for the
        active party — or None (blinding disabled by the caller; the
        uplink is then the raw embedding, which is that caller's explicit
        choice, e.g. the unmasked parity oracle).

        Returns ``(E_parts, uplink)``:
          * E_parts — per-group (G, B, d) local embeddings in group order,
            left SHARDED over the party axis (they never cross a
            collective raw);
          * uplink — (C, B, d) party-order stack of what actually crossed
            the party-axis collective, replicated: [E_k] = E_k + r_k in
            float mode, quantize(E_k) + r_k in Z_2^32 in int32 mode —
            and a ZERO row for the active party: it sends nothing on the
            uplink (paper Alg. 1: it is the receiver); its raw embedding
            enters the round only through ``aggregate_via_active``.
        """
        ax = self.party_axis
        E_parts, ups = [], []
        for (arch, _), idx in self.groups:
            sp = _stack(params, idx)
            sx = jnp.stack([xs[i] for i in idx])
            gm = (None if full_masks is None
                  else self._gather(full_masks, idx))
            # the active party's row inside this group (-1: not here)
            i0 = idx.index(0) if (0 in idx and gm is not None) else -1

            def body(p, x, m, a=arch):
                E = jax.vmap(lambda pi, xi: embed_fn(pi, a, xi))(p, x)
                return E, blinding.blind_uplink(E, m, mask_mode)

            if self._sharded(len(idx)):
                if gm is None:
                    def sh_body(p, x, f=body, i0=i0):
                        E, up = f(p, x, None)
                        return E, jax.lax.all_gather(up, ax, axis=0,
                                                     tiled=True)
                    args = (sp, sx)
                else:
                    def sh_body(p, x, m, f=body, i0=i0):
                        E, up = f(p, x, m)
                        if i0 >= 0:
                            # zero the active row IN-SHARD, before the
                            # collective: its raw embedding must not ride
                            # the uplink gather
                            gids = (jax.lax.axis_index(ax) * up.shape[0]
                                    + jnp.arange(up.shape[0]))
                            keep = (gids != i0).reshape(
                                (-1,) + (1,) * (up.ndim - 1))
                            up = jnp.where(keep, up, jnp.zeros_like(up))
                        return E, jax.lax.all_gather(up, ax, axis=0,
                                                     tiled=True)
                    args = (sp, sx, gm)
                E_loc, up = shard_rules.shard_map(
                    sh_body, self.mesh, in_specs=(P(ax),) * len(args),
                    out_specs=(P(ax), P()))(*args)
            else:
                E_loc, up = body(sp, sx, gm)
                if i0 >= 0:
                    up = up.at[i0].set(0)
            E_parts.append(E_loc)
            ups.append(up)
        return E_parts, self._scatter(ups)

    def embed_blind_uplink_scaled(self, params: Sequence[dict],
                                  xs: Sequence[jnp.ndarray],
                                  full_masks: jnp.ndarray,
                                  mask_mode: str = "int8"):
        """Dynamic-scale twin of ``embed_blind_uplink`` for the int8 wire:
        returns ``(E_parts, uplink, scale)``.

        The int8 ring scale depends on the GLOBAL max |E| over every
        party's embedding, so blinding cannot be fused into the embed
        pass: stage 1 embeds in-shard and all-gathers ONE |E|-max scalar
        per party (the int8 mode's documented magnitude leak — scalars,
        never embedding-shaped wire); the replicated graph folds them
        into the shared ``blinding.ring_scale``; stage 2 blinds in-shard
        under that scale (passed replicated, spec ``P()``) and gathers
        the int8 uplink with the active row zeroed, exactly like the
        unscaled path. fp ``max`` is exact and associative, so the
        two-stage scale is bit-identical to the vectorized engine's
        single ``jnp.max(|E_all|)``.
        """
        assert full_masks is not None and mask_mode == "int8", mask_mode
        ax = self.party_axis
        E_parts, amaxes = [], []
        for (arch, _), idx in self.groups:
            sp = _stack(params, idx)
            sx = jnp.stack([xs[i] for i in idx])

            def body(p, x, a=arch):
                E = jax.vmap(lambda pi, xi: embed_fn(pi, a, xi))(p, x)
                return E, jnp.max(jnp.abs(E), axis=tuple(range(1, E.ndim)))

            if self._sharded(len(idx)):
                def sh_body(p, x, f=body):
                    E, am = f(p, x)
                    return E, jax.lax.all_gather(am, ax, axis=0, tiled=True)
                E_loc, am = shard_rules.shard_map(
                    sh_body, self.mesh, in_specs=(P(ax), P(ax)),
                    out_specs=(P(ax), P()))(sp, sx)
            else:
                E_loc, am = body(sp, sx)
            E_parts.append(E_loc)
            amaxes.append(am)
        scale = blinding.ring_scale(jnp.max(jnp.concatenate(amaxes)),
                                    self.C, mask_mode)
        ups = []
        for g, ((arch, _), idx) in enumerate(self.groups):
            gm = self._gather(full_masks, idx)
            i0 = idx.index(0) if 0 in idx else -1
            if self._sharded(len(idx)):
                def sh_blind(E, m, s, i0=i0):
                    up = blinding.blind_uplink(E, m, mask_mode, s)
                    if i0 >= 0:
                        gids = (jax.lax.axis_index(ax) * up.shape[0]
                                + jnp.arange(up.shape[0]))
                        keep = (gids != i0).reshape(
                            (-1,) + (1,) * (up.ndim - 1))
                        up = jnp.where(keep, up, jnp.zeros_like(up))
                    return jax.lax.all_gather(up, ax, axis=0, tiled=True)
                up = shard_rules.shard_map(
                    sh_blind, self.mesh, in_specs=(P(ax), P(ax), P()),
                    out_specs=P())(E_parts[g], gm, scale)
            else:
                up = blinding.blind_uplink(E_parts[g], gm, mask_mode, scale)
                if i0 >= 0:
                    up = up.at[i0].set(0)
            ups.append(up)
        return E_parts, self._scatter(ups), scale

    def aggregate_via_active(self, E_parts: List[jnp.ndarray],
                             uplink: jnp.ndarray, agg_fn: Callable
                             ) -> jnp.ndarray:
        """Paper Alg. 1 line 6 on the mesh: the ACTIVE party aggregates
        locally and broadcasts the global embedding.

        Party 0 is always local row 0 of the first group's first shard
        (first-seen grouping), so only that device evaluates
        ``agg_fn(E_a_raw, uplink)``; a psum broadcasts the result. The
        downlink collective therefore carries the global embedding E —
        wire every party legitimately receives — and the active party's
        raw embedding never crosses the party axis.
        """
        E0 = E_parts[0]
        n0 = len(self.groups[0][1])
        if not self._sharded(n0):
            return agg_fn(E0[0], uplink)
        ax = self.party_axis

        def body(e_loc, up):
            cand = agg_fn(e_loc[0], up)
            owner = jax.lax.axis_index(ax) == 0
            return jax.lax.psum(
                jnp.where(owner, cand, jnp.zeros_like(cand)), ax)

        return shard_rules.shard_map(
            body, self.mesh, in_specs=(P(ax), P()),
            out_specs=P())(E0, uplink)

    def decide_from(self, params: Sequence[dict], E_parts: List[jnp.ndarray],
                    E_global: jnp.ndarray, view_fn: Callable) -> jnp.ndarray:
        """Stage 2 of the sharded round: per-party decisions on the party
        view of the global embedding.

        ``view_fn(E_global, E_loc) -> E_for_loc`` is applied INSIDE the
        shard (it is the caller's stop-gradient surrogate), so each
        party's raw local embedding is consumed on its own device; only
        the resulting predictions — protocol wire that goes to the active
        party anyway — cross the party-axis collective. Returns
        (C, B, n_classes) replicated, party order.
        """
        ax = self.party_axis
        outs = []
        for g, ((arch, _), idx) in enumerate(self.groups):
            sp = _stack(params, idx)
            E_loc = E_parts[g]

            def body(p, e_loc, e_glob, a=arch):
                e_for = view_fn(e_glob, e_loc)
                return jax.vmap(
                    lambda pi, ei: decide_fn(pi, a, ei))(p, e_for)

            if self._sharded(len(idx)):
                def sh_body(p, e_loc, e_glob, f=body):
                    return jax.lax.all_gather(f(p, e_loc, e_glob), ax,
                                              axis=0, tiled=True)

                out = shard_rules.shard_map(
                    sh_body, self.mesh, in_specs=(P(ax), P(ax), P()),
                    out_specs=P())(sp, E_loc, E_global)
            else:
                out = body(sp, E_loc, E_global)
            outs.append(out)
        return self._scatter(outs)

    # -- grouping-aware optimizer updates ----------------------------------
    def subgroups(self, opts: Sequence[Any]) -> Tuple[Tuple[int, ...], ...]:
        """The (execution-group, optimizer) subgroups, as party-index
        tuples: the members of the ``StackedParties`` that
        ``update_groups`` returns.

        ``opts`` is a per-party list (``optim.resolve_party_optimizers``
        dedupes identical specs to ONE instance, so subgrouping is by
        object identity)."""
        return tuple(tuple(idx[j] for j in pos) for _, idx in self.groups
                     for _, pos in group_by([id(opts[i]) for i in idx]))

    def lay_out(self, stacked: StackedParties) -> StackedParties:
        """Each stack's leading (party) axis over the mesh's party axis
        where it divides evenly, replicated elsewhere; as it is without a
        mesh. Inside a jitted call it fixes the layout the stacks leave
        in, so that the next round lays nothing out again."""
        if self.mesh is None:
            return stacked
        return StackedParties(
            [jax.device_put(st, NamedSharding(
                self.mesh,
                P(self.party_axis) if self._sharded(len(m)) else P()))
             for m, st in zip(stacked.members, stacked.stacks)],
            stacked.members)

    def update_groups(self, opts: Sequence[Any], grads: Sequence[Any],
                      opt_state: Sequence[Any], params: Sequence[Any]
                      ) -> Tuple[StackedParties, StackedParties]:
        """Per-party optimizer updates, one vmapped ``Optimizer.update``
        per (execution-group, optimizer) subgroup (``subgroups``).

        Parties in the same execution group share param/grad/state shapes
        by construction, so each subgroup's trees stack and a single
        ``jax.vmap(opt.update)`` applies the update — the model stays
        vectorized per group while the UPDATE splits per optimizer:
        heterogeneous optimization (paper §IV-E) costs O(#distinct
        optimizers) extra traced ops per group, not O(C). The vmap maps
        the stacked leading axis, so per-party semantics — including each
        party clipping by its OWN gradient norm — are unchanged;
        equivalence with the per-party loop is pinned in
        tests/test_party_optim.py. New params and state come back as
        ``StackedParties`` over those subgroups, laid out by ``lay_out``.
        """
        members = self.subgroups(opts)
        new_p, new_s = [], []
        for sub in members:
            up, us = jax.vmap(opts[sub[0]].update)(
                _stack(grads, sub), _stack(opt_state, sub),
                _stack(params, sub))
            new_p.append(up)
            new_s.append(us)
        return (self.lay_out(StackedParties(new_p, members)),
                self.lay_out(StackedParties(new_s, members)))

    def init_groups(self, opts: Sequence[Any], params: Sequence[Any]
                    ) -> StackedParties:
        """Every party's fresh optimizer state, one vmapped
        ``Optimizer.init`` per ``subgroups`` subgroup."""
        members = self.subgroups(opts)
        return StackedParties(
            [jax.vmap(opts[sub[0]].init)(_stack(params, sub))
             for sub in members], members)

    # -- explicit-vjp protocol path (message-passing reference) ------------
    def embed_vjp(self, params: Sequence[dict], xs: Sequence[jnp.ndarray]):
        """(E_all, pullback): pullback maps gE_all (C,B,d) -> per-party
        embed-net grads (list, party order)."""
        outs, vjps = [], []
        for (arch, _), idx in self.groups:
            sp = _stack(params, idx)
            sx = jnp.stack([xs[i] for i in idx])

            def gf(p, x, a=arch):
                return jax.vmap(lambda pi, xi: embed_fn(pi, a, xi))(p, x)

            if self._sharded(len(idx)):
                gf = self._gathered(gf, 2)
            Eg, vjp_g = jax.vjp(lambda p, f=gf, x=sx: f(p, x), sp)
            outs.append(Eg)
            vjps.append(vjp_g)

        def pull(gE_all: jnp.ndarray) -> List[dict]:
            grads: List[Any] = [None] * self.C
            for (_, idx), vjp_g in zip(self.groups, vjps):
                (gsp,) = vjp_g(self._gather(gE_all, idx))
                for j, i in enumerate(idx):
                    grads[i] = jax.tree.map(lambda x, j=j: x[j], gsp)
            return grads

        return self._scatter(outs), pull

    def decide_vjp(self, params: Sequence[dict], E_per_party: jnp.ndarray):
        """(R_all, pullback): pullback maps gR_all (C,B,n_cls) ->
        (per-party decide-net grads list, gE_all (C,B,d))."""
        outs, vjps = [], []
        for (arch, _), idx in self.groups:
            sp = _stack(params, idx)
            se = self._gather(E_per_party, idx)

            def gf(p, e, a=arch):
                return jax.vmap(lambda pi, ei: decide_fn(pi, a, ei))(p, e)

            if self._sharded(len(idx)):
                gf = self._gathered(gf, 2)
            Rg, vjp_g = jax.vjp(gf, sp, se)
            outs.append(Rg)
            vjps.append(vjp_g)

        def pull(gR_all: jnp.ndarray):
            grads: List[Any] = [None] * self.C
            gEs = []
            for (_, idx), vjp_g in zip(self.groups, vjps):
                gsp, gse = vjp_g(self._gather(gR_all, idx))
                gEs.append(gse)
                for j, i in enumerate(idx):
                    grads[i] = jax.tree.map(lambda x, j=j: x[j], gsp)
            return grads, self._scatter(gEs)

        return self._scatter(outs), pull
