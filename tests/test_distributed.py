"""Distributed integration: the EXACT dry-run step functions executed for
real on a small host-device mesh, checking numerical equality with the
unsharded path (GSPMD correctness for our sharding rules)."""
import os

import numpy as np
import pytest

# needs >1 host device; harmless if already set by the runner
N_DEV = 4
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={N_DEV}"

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from repro import sharding as shard_rules                   # noqa: E402
from repro.configs.base import (EasterConfig, InputShape,    # noqa: E402
                                get_config, smoke_variant)
from repro.launch import steps as steps_mod                 # noqa: E402

pytestmark = pytest.mark.skipif(
    jax.device_count() < N_DEV,
    reason="requires multi-device host (XLA_FLAGS set after jax init)")


def _sys(arch="qwen2.5-3b"):
    cfg = smoke_variant(get_config(arch))
    return steps_mod.make_system(
        cfg, EasterConfig(num_passive=3, d_embed=64, decision_layers=1))


from repro.launch.mesh import make_debug_mesh                # noqa: E402


def _mesh():
    return make_debug_mesh(2, 2)


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "qwen3-moe-235b-a22b",
                                  "mamba2-2.7b"])
def test_sharded_train_step_matches_single_device(arch):
    sys = _sys(arch)
    mesh = _mesh()
    params = sys.init_params(jax.random.PRNGKey(0))
    train_step, opt = steps_mod.build_train_step(sys, "sgd", lr=1e-2)
    opt_state = opt.init(params)
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (4, 16), 0,
                                          sys.cfg.vocab_size),
             "labels": jax.random.randint(jax.random.fold_in(key, 1),
                                          (4, 16), 0, sys.cfg.vocab_size)}
    step_i = jnp.asarray(0, jnp.int32)

    # single-device reference
    _, _, m_ref = jax.jit(train_step)(params, opt_state, batch, step_i)

    specs = {"batch": batch}
    in_sh, out_sh = steps_mod.train_shardings(sys, mesh, specs, params,
                                              opt_state)
    in_sh = steps_mod.to_shardings(mesh, in_sh)
    out_sh = steps_mod.to_shardings(mesh, out_sh)
    with shard_rules.ambient_mesh(mesh), jax.set_mesh(mesh):
        f = jax.jit(train_step, in_shardings=in_sh, out_shardings=out_sh)
        _, _, m_sh = f(params, opt_state, batch, step_i)
    np.testing.assert_allclose(float(m_ref["loss"]), float(m_sh["loss"]),
                               rtol=2e-3)


def test_sharded_serve_step_matches_single_device():
    sys = _sys()
    mesh = _mesh()
    shape = InputShape("d", 16, 4, "decode")
    params = sys.init_params(jax.random.PRNGKey(2))
    serve = steps_mod.build_serve_step(sys, shape)
    B, S = 4, 16
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, 1), 0,
                              sys.cfg.vocab_size)
    caches = sys.init_caches(B, S)
    batch = {"tokens": toks}
    pos = jnp.asarray(3, jnp.int32)

    logits_ref, _ = jax.jit(serve)(params, batch, caches, pos)
    specs = {"batch": batch, "caches": caches, "pos": pos}
    in_sh, out_sh = steps_mod.serve_shardings(sys, mesh, specs, params)
    in_sh = steps_mod.to_shardings(mesh, in_sh)
    out_sh = steps_mod.to_shardings(mesh, out_sh)
    with shard_rules.ambient_mesh(mesh), jax.set_mesh(mesh):
        f = jax.jit(serve, in_shardings=in_sh, out_shardings=out_sh)
        logits_sh, _ = f(params, batch, caches, pos)
    np.testing.assert_allclose(np.asarray(logits_ref, np.float32),
                               np.asarray(logits_sh, np.float32),
                               atol=3e-2, rtol=1e-2)


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
  %ar = f32[256,1024] all-reduce(f32[256,1024] %x), replica_groups={}
  %ag = bf16[64,512] all-gather(bf16[32,512] %y), dimensions={0}
  %junk = f32[8] add(f32[8] %a, f32[8] %b)
  %rs.1 = f32[16,16] reduce-scatter(f32[64,16] %z), dimensions={0}
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 256 * 1024 * 4
    assert out["all-gather"] == 64 * 512 * 2
    assert out["reduce-scatter"] == 16 * 16 * 4
    assert out["count"] == 3
