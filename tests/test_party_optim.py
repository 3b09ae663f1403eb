"""Heterogeneous per-party optimization (paper §IV-E).

``optim.make_party_optimizers`` partitions the update per party subtree
(states in ONE pytree keyed like params), ``PartyEngine.update_groups``
is its grouping-aware vectorized twin (one vmapped update per
(execution-group, optimizer) subgroup), and the whole stack runs inside
the fused train chunk and end-to-end from the launch/train.py CLI —
with every optimizer (sgd / momentum / adagrad / adam) matching the
loop-oracle single-party update and the per-party states surviving a
checkpoint round-trip losslessly.
"""
import json
import os
import sys as _sys

import numpy as np
import pytest

N_DEV = 4
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={N_DEV}"

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from repro import checkpoint, optim                          # noqa: E402
from repro.configs.base import (EasterConfig, get_config,    # noqa: E402
                                smoke_variant)
from repro.core import train_loop                            # noqa: E402
from repro.core.easter_lm import EasterLM                    # noqa: E402
from repro.core.party_models import PartyArch                # noqa: E402
from repro.core.protocol import EasterClassifier             # noqa: E402
from repro.optim import (make_optimizer, make_party_optimizers,  # noqa: E402
                         parse_party_spec, resolve_party_optimizers,
                         split_parties)

NAMES = ("sgd", "momentum", "adagrad", "adam")


# ---------------------------------------------------------------------------
# spec parsing / resolution / state layout
# ---------------------------------------------------------------------------


def test_parse_party_spec():
    spec = parse_party_spec("0=sgd:0.01,1=adagrad:0.005,"
                            "2=momentum:0.01:momentum=0.8")
    assert spec == {0: ("sgd", 0.01, {}), 1: ("adagrad", 0.005, {}),
                    2: ("momentum", 0.01, {"momentum": 0.8})}
    with pytest.raises(ValueError):
        parse_party_spec("0=nadam:0.1")          # unknown optimizer
    with pytest.raises(ValueError):
        parse_party_spec("sgd:0.1")              # missing party index
    with pytest.raises(ValueError):
        parse_party_spec("0=sgd:0.1,0=adam:0.1")  # duplicate party
    with pytest.raises(ValueError):
        parse_party_spec("0=sgd")                # lr is required


def test_resolve_dedupes_identical_specs():
    """Identical (name, lr, hparams) resolve to ONE instance — the
    identity PartyEngine.update_groups subgroups by."""
    opts = resolve_party_optimizers(
        {0: ("sgd", 0.01), 2: ("sgd", 0.01), 3: ("sgd", 0.02)}, 4,
        default=("adam", 1e-3, None))
    assert opts[0] is opts[2]
    assert opts[0] is not opts[3]                # different lr
    assert opts[1].name == "adam"                # default fill
    with pytest.raises(ValueError):
        resolve_party_optimizers({7: ("sgd", 0.01)}, 4)


def _tiny_params_lm(C=3):
    return {"parties": [{"w": jnp.full((2, 2), float(k + 1)),
                         "b": jnp.zeros((2,))} for k in range(C)]}


def test_party_optimizer_state_keyed_like_params():
    """init keeps the param container ({"parties": [...]} and plain
    lists), with party k's subtree under party k's optimizer."""
    popt = make_party_optimizers(
        {0: ("sgd", 1e-2), 1: ("adam", 1e-3), 2: ("adagrad", 1e-2)}, 3)
    assert popt.name == "party(sgd,adam,adagrad)"
    params = _tiny_params_lm()
    state = popt.init(params)
    assert set(state) == {"parties"}
    assert state["parties"][0] == {}                      # sgd: stateless
    assert set(state["parties"][1]) == {"m", "v", "t"}    # adam
    assert set(state["parties"][2]) == {"s"}              # adagrad
    # plain-list container (EasterClassifier layout)
    lst = params["parties"]
    state_l = popt.init(lst)
    assert isinstance(state_l, list) and state_l[0] == {}
    with pytest.raises(ValueError):
        popt.init(_tiny_params_lm(C=4))          # party-count mismatch
    with pytest.raises(TypeError):
        split_parties(42)


def test_party_optimizer_updates_each_subtree_with_its_own_rule():
    popt = make_party_optimizers({0: ("sgd", 0.5), 1: ("sgd", 0.1)}, 2)
    params = _tiny_params_lm(C=2)
    grads = jax.tree.map(jnp.ones_like, params)
    new_p, new_s = popt.update(grads, popt.init(params), params)
    np.testing.assert_allclose(np.asarray(new_p["parties"][0]["w"]),
                               np.asarray(params["parties"][0]["w"]) - 0.5)
    np.testing.assert_allclose(np.asarray(new_p["parties"][1]["w"]),
                               np.asarray(params["parties"][1]["w"]) - 0.1)
    assert new_s == {"parties": [{}, {}]}


# ---------------------------------------------------------------------------
# grouping-aware stacked updates == per-party loop (paper scale)
# ---------------------------------------------------------------------------


def _classifier(engine="vectorized", C=6):
    # one arch repeated -> ONE execution group of 6 parties, so optimizer
    # subgrouping inside a group is actually exercised
    arches = [PartyArch("mlp", (32, 16), (16,), 24, 5) for _ in range(C)]
    nf = [8] * C
    e = EasterConfig(num_passive=C - 1, d_embed=24)
    return EasterClassifier(e, arches, nf, engine=engine)


def test_update_groups_matches_party_loop():
    sys_ = _classifier()
    C = sys_.C
    key = jax.random.PRNGKey(0)
    params = sys_.init_params(key)
    opts = resolve_party_optimizers(
        {k: (NAMES[k % 4], 1e-2 + 1e-3 * (k % 2)) for k in range(C)}, C)
    states = [opts[k].init(params[k]) for k in range(C)]
    grads = [jax.tree.map(
        lambda x, k=k: jax.random.normal(jax.random.fold_in(key, k),
                                         x.shape, x.dtype), params[k])
        for k in range(C)]
    gp, gs = sys_._eng.update_groups(opts, grads, states, params)
    for k in range(C):
        p, s = opts[k].update(grads[k], states[k], params[k])
        for a, b in zip(jax.tree.leaves(gp[k]), jax.tree.leaves(p)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-7, atol=1e-9)
        for a, b in zip(jax.tree.leaves(gs[k]), jax.tree.leaves(s)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-7, atol=1e-9)


def test_classifier_train_step_party_optimizers_engine_parity():
    """The jitted paper-scale train step with heterogeneous optimizers:
    vectorized grouped updates vs the loop-engine per-party oracle."""
    sv, sl = _classifier("vectorized"), _classifier("loop")
    spec = {k: (NAMES[k % 4], 1e-2) for k in range(sv.C)}
    params = sv.init_params(jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(2)
    xs = [jax.random.normal(jax.random.fold_in(key, k), (6, 8))
          for k in range(sv.C)]
    y = jax.random.randint(jax.random.fold_in(key, 99), (6,), 0, 5)
    masks = sv.masks(6, 0)
    init_v, step_v = sv.make_train_step("adam", 1e-3,
                                        party_optimizers=spec)
    init_l, step_l = sl.make_train_step("adam", 1e-3,
                                        party_optimizers=spec)
    pv, sv_state, tv, _ = step_v(params, init_v(params), xs, y, masks)
    pl, sl_state, tl, _ = step_l(params, init_l(params), xs, y, masks)
    np.testing.assert_allclose(float(tv), float(tl), rtol=1e-6)
    # the grouped step carries stacks per (group, optimizer) subgroup, so
    # its leaves are ordered by subgroup: compare party by party
    for k in range(sv.C):
        a_k, b_k = (pv[k], sv_state[k]), (pl[k], sl_state[k])
        assert jax.tree.structure(a_k) == jax.tree.structure(b_k)
        for a, b in zip(jax.tree.leaves(a_k), jax.tree.leaves(b_k)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# optimizer parity inside the fused train chunk (LLM scale)
# ---------------------------------------------------------------------------

B, S = 2, 8
D_EMBED = 32


def _lm(mask_mode="float"):
    cfg = smoke_variant(get_config("qwen2.5-3b"))
    e = EasterConfig(num_passive=3, d_embed=D_EMBED, decision_layers=1,
                     mask_mode=mask_mode)
    return EasterLM(cfg=cfg, easter=e, engine="vectorized")


@pytest.mark.parametrize("mask_mode", ["float", "int32"])
def test_party_optimizers_in_chunk_match_single_party_oracle(mask_mode):
    """Each of sgd/momentum/adagrad/adam as a party-local optimizer
    inside ``train_chunk`` matches the loop-oracle single-party update
    (that party's own make_optimizer applied to that party's own grad
    subtree) to ~1 ulp — and the int32 wire format leaves optimizer
    behaviour untouched (masks cancel before the loss)."""
    sys_ = _lm(mask_mode)
    C = sys_.C                                   # 4: one of each optimizer
    specs = {k: (NAMES[k], 1e-2 if NAMES[k] != "adam" else 1e-3)
             for k in range(C)}
    popt = make_party_optimizers(specs, C)
    params = sys_.init_params(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, B, S + 1), 0,
                              sys_.cfg.vocab_size)
    batches = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
    fn = train_loop.build_train_chunk(sys_, popt, donate=False)
    p_c, s_c, _, _ = fn(params, popt.init(params), batches,
                        jnp.asarray(0, jnp.int32))

    seeds = sys_.mask_seeds()
    b0 = jax.tree.map(lambda x: x[0], batches)
    grads = jax.jit(jax.grad(
        lambda p: sys_.loss_fn(p, b0, jnp.asarray(0, jnp.int32),
                               seeds)[0]))(params)
    for k in range(C):
        opt_k = make_optimizer(*specs[k][:2])
        p_k, s_k = opt_k.update(grads["parties"][k],
                                opt_k.init(params["parties"][k]),
                                params["parties"][k])
        for a, b in zip(jax.tree.leaves(p_c["parties"][k]),
                        jax.tree.leaves(p_k)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-6, atol=3e-7,
                                       err_msg=f"party {k} ({NAMES[k]})")
        for a, b in zip(jax.tree.leaves(s_c["parties"][k]),
                        jax.tree.leaves(s_k)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-6, atol=3e-7,
                                       err_msg=f"party {k} ({NAMES[k]})")


# ---------------------------------------------------------------------------
# end-to-end: the launch/train.py CLI + lossless checkpoint round-trip
# ---------------------------------------------------------------------------


def test_train_cli_party_optimizers_checkpoint(tmp_path, monkeypatch):
    """Heterogeneous per-party optimizers end-to-end from the CLI: the
    fused-chunk launcher runs, checkpoints, and the saved per-party
    optimizer states restore losslessly (bit-identical array round
    trip); a --resume run picks the state up and continues."""
    from repro.launch import train as train_mod
    monkeypatch.chdir(tmp_path)
    ck = str(tmp_path / "ck.npz")
    argv = ["train", "--arch", "qwen2.5-3b", "--smoke", "--steps", "3",
            "--chunk", "2", "--batch", "2", "--seq", "8",
            "--num-passive", "2", "--d-embed", "32", "--log-every", "1",
            "--party-optimizers", "0=sgd:0.01,1=adagrad:0.005",
            "--ckpt", ck, "--ckpt-every", "2"]
    monkeypatch.setattr(_sys, "argv", argv)
    train_mod.main()
    hist = json.load(open(tmp_path / "experiments/train/"
                          "qwen2.5-3b_train.json"))
    assert len(hist["history"]) == 3
    assert np.isfinite([h["loss"] for h in hist["history"]]).all()

    # lossless state round-trip: restore into zeroed templates and
    # compare bit-for-bit against the raw npz payload
    sys_ = EasterLM(cfg=smoke_variant(get_config("qwen2.5-3b")),
                    easter=EasterConfig(num_passive=2, d_embed=32))
    popt = make_party_optimizers(
        parse_party_spec("0=sgd:0.01,1=adagrad:0.005"), sys_.C,
        default=("adam", 1e-3, {"grad_clip": 1.0}))
    params0 = sys_.init_params(jax.random.PRNGKey(0))
    like = jax.tree.map(jnp.zeros_like,
                        {"params": params0, "opt": popt.init(params0)})
    state, step0 = checkpoint.restore(ck, like)
    assert step0 == 3
    assert set(state["opt"]["parties"][1]) == {"s"}       # adagrad
    assert set(state["opt"]["parties"][2]) == {"m", "v", "t"}  # default adam
    resaved = str(tmp_path / "resaved.npz")
    checkpoint.save(resaved, state, step=step0)
    with np.load(ck) as a, np.load(resaved) as b:
        assert set(a.files) == set(b.files)
        for f in a.files:
            np.testing.assert_array_equal(a[f], b[f])

    # and --resume continues from the restored heterogeneous state
    monkeypatch.setattr(_sys, "argv", argv + ["--resume", "--steps", "1"])
    train_mod.main()
