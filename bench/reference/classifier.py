"""Plain reference of the EASTER classifier's training rounds (paper Alg. 1).

Written from the paper, not from the program, and importing nothing of it.
Every party k embeds its own feature slice with its MLP embedding net,
``E_k = h_k(x_k)``; the active party averages ``E = (1/C) sum_k E_k`` (the
pairwise masks cancel in this sum, so the reference leaves them out); party
k decides ``R_k = p_k(E)`` and takes the cross-entropy ``L_k`` against the
labels; and party k's gradient is that of its own loss alone, reaching its
embedding net only through its own share ``E_k / C`` of the aggregate.
Each party then takes one Adam step.

Parameters are the benchmark's own (``bench.weights``), as a per-party list
of ``{"embed": {"layers": [{"w", "b"}...]}, "decide": {"layers": [...]}}``.
Parties of one shape are stacked and mapped, which changes no arithmetic.
The reference runs in float32 at full matmul precision; ``dtype=bfloat16``
computes it in bfloat16 instead (the control).
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def _mlp(layers, x, relu_last: bool):
    import jax
    for i, lp in enumerate(layers):
        x = x @ lp["w"] + lp["b"]
        if i < len(layers) - 1 or relu_last:
            x = jax.nn.relu(x)
    return x


def _xent(logits, y):
    import jax
    import jax.numpy as jnp
    logz = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logz, y[:, None], axis=-1))


def _groups(params, xs) -> List[List[int]]:
    """Parties with the same parameter shapes and slice width."""
    import jax
    sig: Dict[tuple, List[int]] = {}
    for k, (p, x) in enumerate(zip(params, xs)):
        key = (tuple(l.shape for l in jax.tree.leaves(p)), x.shape[-1])
        sig.setdefault(key, []).append(k)
    return list(sig.values())


def follow(params0, batches: Sequence[tuple], *, lr: float, b1: float,
           b2: float, eps: float, dtype: str = "float32") -> dict:
    """Run ``len(batches)`` rounds from ``params0``, in one compiled call.

    ``batches``: ``[(xs, y), ...]`` with ``xs`` the per-party slices.
    Returns per-round per-party losses ``(steps, C)``, and per party and
    leaf (in ``jax.tree.leaves`` order) the norm of the first gradient and
    of the change of the parameters over all rounds.
    """
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    C = len(params0)
    groups = _groups(params0, batches[0][0])
    stack = lambda ts: jax.tree.map(lambda *a: jnp.stack(a), *ts)  # noqa
    cast = lambda t: jax.tree.map(lambda a: a.astype(dt), t)  # noqa

    def loss_all(gp, gx, y):
        # embeddings of every group, in party order within the group
        E_g = [jax.vmap(lambda p, x: _mlp(p["embed"]["layers"], x, False))(
            p, x) for p, x in zip(gp, gx)]
        E = sum(jnp.sum(e, axis=0) for e in E_g) / C
        total, per = 0.0, []
        for p, e in zip(gp, E_g):
            other = jax.lax.stop_gradient(E[None] - e / C)

            def one(pk, ek, ok):
                logits = _mlp(pk["decide"]["layers"], ok + ek / C, False)
                return _xent(logits, y)

            lk = jax.vmap(one)(p, e, other)
            per.append(lk)
            total = total + jnp.sum(lk)
        return total, per

    def adam(p, g, m, v, t):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        step = lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return (p - step).astype(dt), m, v

    def leaf_norms(tree):
        """Per group, per leaf: the norm of each member party's leaf."""
        return [[jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32)),
                                  axis=tuple(range(1, l.ndim))))
                 for l in jax.tree.leaves(g)] for g in tree]

    @jax.jit
    def rounds(params, xss, ys):
        gp = [cast(stack([params[k] for k in idx])) for idx in groups]
        p_init = gp
        gm = jax.tree.map(jnp.zeros_like, gp)
        gv = jax.tree.map(jnp.zeros_like, gp)
        losses, grad1 = [], None
        for i, (xs, y) in enumerate(zip(xss, ys)):
            gx = [jnp.stack([xs[k].astype(dt) for k in idx])
                  for idx in groups]
            (_, per), g = jax.value_and_grad(loss_all, has_aux=True)(
                gp, gx, y)
            out = jax.tree.map(lambda p, gg, m, v: adam(p, gg, m, v, i + 1.0),
                               gp, g, gm, gv)
            pick = lambda j: jax.tree.map(  # noqa: E731
                lambda o: o[j], out, is_leaf=lambda o: isinstance(o, tuple))
            gp, gm, gv = pick(0), pick(1), pick(2)
            losses.append(per)
            if grad1 is None:
                grad1 = leaf_norms(g)
        change = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                              - b.astype(jnp.float32), gp, p_init)
        return losses, grad1, leaf_norms(change)

    precision = "highest" if dt == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        losses, grad1, change = jax.device_get(rounds(
            params0, [list(xs) for xs, _ in batches],
            [y for _, y in batches]))

    def per_party(norms):
        out = [None] * C
        for idx, leaves in zip(groups, norms):
            for j, k in enumerate(idx):
                out[k] = [float(l[j]) for l in leaves]
        return out

    loss = np.zeros((len(batches), C))
    for s, per in enumerate(losses):
        for idx, lk in zip(groups, per):
            loss[s, idx] = np.asarray(lk, np.float64)
    return {"loss": loss, "grad1": per_party(grad1),
            "change": per_party(change)}
