"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

Nothing runs on a chip here: each case lowers a kernel for a described
(not attached) ``v5e:2x2`` topology and compiles it with the installed
TPU compiler, which refuses what the chip's compiler would refuse —
misaligned blocks, unsupported PRNG seeding, SMEM tiling. Interpret-mode
tests cannot see any of that.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import blinding
from repro.kernels import blind_agg as ba


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *avals) -> str:
    text = jax.jit(fn).lower(*avals).compile().as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel was not lowered"
    return text


@pytest.mark.parametrize("K,N,d,dtype", [
    (3, 2048, 128, jnp.float32),
    (3, 2048, 128, jnp.bfloat16),
    (63, 1024, 128, jnp.float32),
    (3, 300, 128, jnp.float32),
    (3, 300, 128, jnp.bfloat16),
])
def test_blind_agg_fwd_bwd_compiles(one_chip, K, N, d, dtype):
    def s(shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fwd_bwd(ea, ep, mk, g):
        out, pull = jax.vjp(ba.blind_agg, ea, ep, mk)
        return out, pull(g)         # traced cotangent: bwd kernel compiled

    _compile(fwd_bwd, s((N, d)), s((K, N, d)), s((K, N, d)), s((N, d)))


@pytest.mark.parametrize("K,N,d", [(3, 2048, 128), (3, 300, 128),
                                   (63, 1024, 128)])
def test_prng_blind_agg_fwd_bwd_compiles(one_chip, K, N, d):
    eng = blinding.setup_mask_engine(K, deterministic_seed=3)
    fn = ba.make_prng_blind_agg(eng.seed_hi, eng.seed_lo, eng.signs)

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def fwd_bwd(ea, ep, words, g):
        out, pull = jax.vjp(fn, ea, ep, words)
        return out, pull(g)

    text = _compile(fwd_bwd, s((N, d)), s((K, N, d)), s((2,)), s((N, d)))
    assert text.count("tpu_custom_call") >= 2      # fused fwd + bcast bwd
