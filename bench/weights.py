"""Weights made from the seed, on the device, in one jitted call.

The benchmark makes the weights itself, so that the plain reference can
make the very same ones from the seed without taking anything the program
made. ``fill`` takes the program's parameter shapes (``jax.eval_shape`` of
its ``init_params``) and draws every leaf by the init's distribution, read
from the leaf's name: a weight ``w`` is normal over the square root of its
fan-in (the second-last axis, so a stack of layers draws like one layer),
an embedding ``table`` unit normal, a norm ``scale`` ones, a bias ``b`` or
``bias`` zeros. Weights that are served untrained would leave a program
that drops its biases or norm scales unseen, so ``bias_std`` and
``scale_std`` can draw biases as normals and scales as one plus a normal.
"""
from __future__ import annotations

import functools
import math


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _draw(key, name: str, shape, dtype, bias_std: float, scale_std: float):
    import jax
    import jax.numpy as jnp
    if name in ("b", "bias"):
        return (jax.random.normal(key, shape, jnp.float32)
                * bias_std).astype(dtype)
    if name == "scale":
        return (1.0 + jax.random.normal(key, shape, jnp.float32)
                * scale_std).astype(dtype)
    if name == "table":
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)
    if name == "w":
        fan_in = shape[-2] if len(shape) >= 2 else shape[0]
        s = 1.0 / math.sqrt(max(1, fan_in))
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)
    raise ValueError(f"no init rule for a parameter named {name!r}")


def fill(shapes, key, *, bias_std: float = 0.0, scale_std: float = 0.0):
    """Parameters shaped like ``shapes``, drawn from ``key`` on device."""
    import jax
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = tuple((_leaf_name(p), tuple(s.shape), str(s.dtype))
                  for p, s in paths)
    return _maker(treedef, specs, float(bias_std), float(scale_std))(key)


@functools.lru_cache(maxsize=8)
def _maker(treedef, specs, bias_std, scale_std):
    """One jitted maker per parameter layout, so that a process that makes
    the same weights again (the reference does) does not trace again."""
    import jax

    def make(k):
        leaves = [_draw(jax.random.fold_in(k, i), n, sh, dt, bias_std,
                        scale_std)
                  for i, (n, sh, dt) in enumerate(specs)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)
