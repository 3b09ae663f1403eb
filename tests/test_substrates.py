"""Substrate tests: optimizers, data pipeline, checkpointing, sharding."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro import checkpoint
from repro import sharding as shard_rules
from repro.data import make_dataset, vertical_partition
from repro.data.pipeline import Prefetcher, batch_iterator, slice_hw
from repro.optim import clip_by_global_norm, global_norm, make_optimizer


# ---------------------------------------------------------------------------
# optim
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["sgd", "momentum", "adagrad", "adam"])
def test_optimizers_minimize_quadratic(name):
    # adagrad's effective lr decays as 1/sqrt(sum g^2) — needs a larger base
    opt = make_optimizer(name, 1.0 if name == "adagrad" else 0.1)
    params = {"w": jnp.array([3.0, -2.0]), "b": jnp.array(1.5)}
    state = opt.init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + p["b"] ** 2

    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state = opt.update(g, state, params)
    assert float(loss(params)) < 1e-2, name


def test_momentum_matches_manual():
    opt = make_optimizer("momentum", 0.1, momentum=0.9)
    p = {"w": jnp.array(1.0)}
    s = opt.init(p)
    g = {"w": jnp.array(2.0)}
    p1, s1 = opt.update(g, s, p)
    assert np.isclose(float(p1["w"]), 1.0 - 0.1 * 2.0)
    p2, _ = opt.update(g, s1, p1)
    assert np.isclose(float(p2["w"]), float(p1["w"]) - 0.1 * (0.9 * 2 + 2))


def test_grad_clip():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, n = clip_by_global_norm(g, 1.0)
    assert np.isclose(float(global_norm(clipped)), 1.0, atol=1e-5)


def test_adam_bf16_params_fp32_state():
    opt = make_optimizer("adam", 1e-2)
    p = {"w": jnp.ones((4,), jnp.bfloat16)}
    s = opt.init(p)
    assert s["m"]["w"].dtype == jnp.float32
    p2, _ = opt.update({"w": jnp.ones((4,), jnp.bfloat16)}, s, p)
    assert p2["w"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(C=st.integers(1, 8), F=st.integers(8, 64))
def test_vertical_partition_covers_features(C, F):
    x = np.arange(2 * F, dtype=np.float32).reshape(2, F)
    parts = vertical_partition(x, C)
    assert sum(p.shape[-1] for p in parts) == F
    np.testing.assert_array_equal(np.concatenate(parts, -1), x)


def test_vertical_partition_image_strips():
    x = np.random.rand(3, 28 * 28).astype(np.float32)
    parts = vertical_partition(x, 4, image_hw=(28, 28))
    assert sum(p.shape[-1] for p in parts) == 28 * 28
    hws = slice_hw((28, 28), 4)
    assert [h * w for h, w in hws] == [p.shape[-1] for p in parts]


def test_datasets_all_names():
    for name in ["mnist_like", "fmnist_like", "cifar_like", "cifar100_like",
                 "cinic_like", "criteo_like"]:
        ds = make_dataset(name, n_train=64, n_test=32)
        assert ds.x_train.shape[0] == 64
        assert ds.y_train.max() < ds.n_classes
        assert np.isfinite(ds.x_train).all()


def test_batch_iterator_and_prefetch():
    x = np.arange(100, dtype=np.float32)[:, None]
    y = np.arange(100, dtype=np.int32)
    it = Prefetcher(iter([next(batch_iterator(x, y, 32)) for _ in range(5)]))
    batches = list(it)
    assert len(batches) == 5
    assert batches[0][0].shape == (32, 1)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": {"b": jnp.arange(6.0).reshape(2, 3),
                  "c": [jnp.ones((2,), jnp.bfloat16), jnp.zeros((1,))]},
            "d": jnp.asarray(3)}
    path = os.path.join(tmp_path, "ckpt.npz")
    checkpoint.save(path, tree, step=17)
    restored, step = checkpoint.restore(path, tree)
    assert step == 17
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_param_specs_cover_model_zoo():
    from repro.configs.base import get_config, smoke_variant
    from repro.models import build
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(1, 1)
    for arch in ["qwen2.5-3b", "qwen3-moe-235b-a22b", "mamba2-2.7b",
                 "recurrentgemma-9b"]:
        cfg = smoke_variant(get_config(arch))
        params = jax.eval_shape(lambda: build(cfg).init(jax.random.PRNGKey(0)))
        specs = shard_rules.param_specs(params, mesh)
        # spec rank never exceeds leaf rank
        for leaf, sp in zip(jax.tree.leaves(params),
                            jax.tree.leaves(specs,
                                            is_leaf=lambda x: isinstance(x, P))):
            assert len(sp) <= leaf.ndim, (sp, leaf.shape)


def test_fsdp_overlay_shards_large_leaves():
    # AbstractMesh: spec logic only, no physical devices needed
    mesh = jax.sharding.AbstractMesh((2, 2), ("data", "model"))
    leaf = jax.ShapeDtypeStruct((8, 1024, 2048), jnp.float32)
    sp = shard_rules._add_fsdp(P(None, None, "model"), leaf, mesh)
    assert any(e == "data" or e == ("data",) for e in sp)
    small = jax.ShapeDtypeStruct((16,), jnp.float32)
    assert shard_rules._add_fsdp(P(None), small, mesh) == P(None)
