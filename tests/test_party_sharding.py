"""Mesh-sharded party engine (core/party_engine.py mesh mode).

The grouped-vmap engine laid out over a "party" mesh axis with shard_map
must reproduce the single-device vectorized engine BIT-EXACTLY on every
forward path (embeds, losses, serve/prefill logits, mask synthesis) and
to a few ulp on grads (XLA fuses the shard-local vjp bodies differently).
The trust-boundary property is audited structurally: the only party-axis
collective carrying embedding-shaped tensors consumes the BLINDED uplink
[E_k] = E_k + r_k, never a raw local embedding.
"""
import os

import numpy as np
import pytest

# needs >1 host device; harmless if already set by the runner/conftest
N_DEV = 4
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={N_DEV}"

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from repro.configs.base import (EasterConfig, get_config,    # noqa: E402
                                smoke_variant)
from repro.core import blinding                              # noqa: E402
from repro.core.easter_lm import EasterLM                    # noqa: E402
from repro.core.party_models import PartyArch                # noqa: E402
from repro.core.protocol import EasterClassifier             # noqa: E402
from repro.launch.mesh import make_party_mesh                # noqa: E402

pytestmark = pytest.mark.skipif(
    jax.device_count() < N_DEV,
    reason="requires multi-device host (XLA_FLAGS set after jax init)")

D_EMBED, N_CLS, B = 24, 5, 6


def _mk(engine, mask_mode="float", C=8, grad_mode="easter"):
    """Two MLP signatures, alternating -> two groups of C/2 parties each
    (divisible by the 4-way party axis when C=8)."""
    arches = [PartyArch("mlp", (32, 16) if k % 2 == 0 else (48,), (16,),
                        D_EMBED, N_CLS) for k in range(C)]
    nf = [10] * C
    e = EasterConfig(num_passive=C - 1, d_embed=D_EMBED,
                     mask_mode=mask_mode)
    return EasterClassifier(e, arches, nf, engine=engine,
                            grad_mode=grad_mode)


def _batch(sys, seed=0):
    key = jax.random.PRNGKey(seed)
    xs = [jax.random.normal(jax.random.fold_in(key, k),
                            (B, sys.n_features[k])) for k in range(sys.C)]
    y = jax.random.randint(jax.random.fold_in(key, 99), (B,), 0, N_CLS)
    return xs, y


def _grads_close(ga, gb, atol=5e-6):
    """Sharded backward == vectorized backward to fusion noise (~1 ulp)."""
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=atol, rtol=1e-6)


# ---------------------------------------------------------------------------
# classifier: sharded == vectorized
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mask_mode", ["float", "int32"])
@pytest.mark.parametrize("masked", [True, False])
def test_classifier_sharded_loss_bitexact(mask_mode, masked):
    sv = _mk("vectorized", mask_mode)
    ss = _mk("sharded", mask_mode)
    _check_loss_and_grads(sv, ss, masked)


def test_classifier_sharded_joint_mode():
    """grad_mode="joint" backprops THROUGH the aggregate — i.e. through
    the uplink gather and the active-aggregate psum downlink."""
    _check_loss_and_grads(_mk("vectorized", grad_mode="joint"),
                          _mk("sharded", grad_mode="joint"), True)


def _check_loss_and_grads(sv, ss, masked):
    assert ss._eng._sharded(4)          # two groups of 4 over a 4-way axis
    params = sv.init_params(jax.random.PRNGKey(1))
    xs, y = _batch(sv)
    masks = sv.masks(B, 0) if masked else None
    lv, pv = sv.loss_fn(params, xs, y, masks)
    ls, ps = ss.loss_fn(params, xs, y, masks)
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(ls))
    np.testing.assert_array_equal(np.asarray(pv), np.asarray(ps))
    gv = jax.grad(lambda p: sv.loss_fn(p, xs, y, masks)[0])(params)
    gs = jax.grad(lambda p: ss.loss_fn(p, xs, y, masks)[0])(params)
    _grads_close(gv, gs)


def test_classifier_sharded_forward_and_assisted():
    sv, ss = _mk("vectorized"), _mk("sharded")
    params = sv.init_params(jax.random.PRNGKey(2))
    xs, y = _batch(sv, seed=3)
    np.testing.assert_array_equal(
        np.asarray(sv.local_embeds(params, xs)),
        np.asarray(ss.local_embeds(params, xs)))
    ga, La = sv.assisted_grads(params, xs, y, None)
    gb, Lb = ss.assisted_grads(params, xs, y, None)
    np.testing.assert_array_equal(np.asarray(La), np.asarray(Lb))
    _grads_close(ga, gb)


def test_classifier_sharded_jitted_train_step():
    sv, ss = _mk("vectorized"), _mk("sharded")
    params = sv.init_params(jax.random.PRNGKey(4))
    xs, y = _batch(sv, seed=5)
    masks = ss.masks(B, 0)
    _, step_v = sv.make_train_step("adam", 1e-3)
    init_s, step_s = ss.make_train_step("adam", 1e-3)
    out_v = step_v(params, init_s(params), xs, y, masks)
    out_s = step_s(params, init_s(params), xs, y, masks)
    np.testing.assert_array_equal(np.asarray(out_v[2]), np.asarray(out_s[2]))


def test_classifier_uneven_group_falls_back_correctly():
    """C=6 -> two groups of 3: 3 doesn't divide the 4-way axis, so the
    engine must silently run those groups unsharded — same results."""
    sv = _mk("vectorized", C=6)
    ss = _mk("sharded", C=6)
    assert not ss._eng._sharded(3)
    params = sv.init_params(jax.random.PRNGKey(6))
    xs, y = _batch(sv, seed=7)
    masks = sv.masks(B, 1)
    lv, pv = sv.loss_fn(params, xs, y, masks)
    ls, ps = ss.loss_fn(params, xs, y, masks)
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(ls))
    np.testing.assert_array_equal(np.asarray(pv), np.asarray(ps))


# ---------------------------------------------------------------------------
# mask synthesis: per-group sharded MaskEngine == replicated MaskEngine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mask_mode", ["float", "int32"])
def test_mask_engine_sharded_synthesis_bitexact(mask_mode):
    eng = blinding.cached_mask_engine(8, 7)
    mesh = make_party_mesh(4)
    for r in (0, 3):
        ref = eng.masks((B, D_EMBED), r, mask_mode)
        sh = eng.masks((B, D_EMBED), r, mask_mode, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(sh))
    # non-divisible K falls back to the replicated synthesis
    eng5 = blinding.cached_mask_engine(5, 7)
    np.testing.assert_array_equal(
        np.asarray(eng5.masks((B, D_EMBED), 1, mask_mode)),
        np.asarray(eng5.masks((B, D_EMBED), 1, mask_mode, mesh=mesh)))


# ---------------------------------------------------------------------------
# trust boundary: only BLINDED tensors cross the party-axis collective
# ---------------------------------------------------------------------------


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield jaxpr, eqn
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)       # ClosedJaxpr -> Jaxpr
            if sub is not None and hasattr(sub, "eqns"):
                yield from _iter_eqns(sub)
            elif hasattr(v, "eqns"):              # raw Jaxpr
                yield from _iter_eqns(v)


def _producer(jaxpr, var):
    for eqn in jaxpr.eqns:
        if any(o is var for o in eqn.outvars):
            return eqn
    return None


def _leaf_producer(jaxpr, var):
    """Producer eqn of ``var``, descending through outlined ``jit`` calls."""
    eqn = _producer(jaxpr, var)
    while eqn is not None and eqn.primitive.name == "jit":
        closed = eqn.params["jaxpr"]
        inner = getattr(closed, "jaxpr", closed)
        pos = next(i for i, o in enumerate(eqn.outvars) if o is var)
        var = inner.outvars[pos]
        if not hasattr(var, "count"):         # literal output
            return None
        jaxpr, eqn = inner, _producer(inner, var)
    return eqn


def test_only_blinded_tensors_cross_party_collective():
    """Structural audit of the sharded training round's jaxpr. The only
    party-axis collectives are protocol wire: (1) all_gathers of
    embedding-shaped tensors must consume the mask ADD (the blinded
    uplink) or the active-row zeroing select that follows it — never a
    raw embedding; (2) exactly one psum, the paper's line-6 downlink of
    the active-party aggregate; (3) all_gathers of the predictions."""
    ss = _mk("sharded")
    params = ss.init_params(jax.random.PRNGKey(8))
    xs, y = _batch(ss, seed=9)
    masks = ss.masks(B, 0)
    closed = jax.make_jaxpr(lambda p: ss.loss_fn(p, xs, y, masks)[0])(params)

    gathers, psums, others = [], [], []
    for jaxpr, eqn in _iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if "all_gather" in name:
            gathers.append((jaxpr, eqn))
        elif name == "psum":
            psums.append((jaxpr, eqn))
        elif any(c in name for c in ("ppermute", "all_to_all",
                                     "pmax", "pmin")):
            others.append(name)
    assert not others, f"unexpected collectives in forward round: {others}"
    # the downlink: ONE psum broadcasting the active party's aggregate
    assert len(psums) == 1
    # two groups x (embed uplink + decision gather)
    assert len(gathers) == 2 * ss._eng.n_groups

    embed_gathers = [(j, e) for j, e in gathers
                     if e.invars[0].aval.shape[-1] == D_EMBED]
    decide_gathers = [(j, e) for j, e in gathers
                      if e.invars[0].aval.shape[-1] == N_CLS]
    assert len(embed_gathers) == ss._eng.n_groups
    assert len(decide_gathers) == ss._eng.n_groups
    for jaxpr, eqn in embed_gathers:
        prod = _leaf_producer(jaxpr, eqn.invars[0])
        assert prod is not None, \
            "party collective consumes a raw shard input"
        # the group holding the active party zeroes its row (select_n)
        # AFTER blinding; every other group's gather consumes the mask
        # add directly. (That the select's kept branch is the blinded
        # add — not a raw embedding — is pinned at the VALUE level by
        # test_uplink_payload_is_blinded.)
        assert prod.primitive.name in ("add", "select_n"), \
            f"embedding uplink gathered without blinding (via " \
            f"{prod.primitive.name})"


def test_uplink_payload_is_blinded():
    """Value-level audit: what the stage-1 collective carries equals
    E_raw + r for every PASSIVE party (never the raw embedding), is
    EXACTLY ZERO for the active party (it sends nothing on the uplink —
    its embedding enters only via the aggregate-downlink psum), and the
    masks cancel."""
    ss = _mk("sharded")
    sv = _mk("vectorized")
    params = ss.init_params(jax.random.PRNGKey(10))
    xs, _ = _batch(ss, seed=11)
    masks = ss.masks(B, 2)
    full = jnp.concatenate(
        [jnp.zeros((1,) + masks.shape[1:], masks.dtype), masks], 0)
    _, up = ss._eng.embed_blind_uplink(params, xs, full, "float")
    E_raw = sv.local_embeds(params, xs)
    assert np.all(np.asarray(up[0]) == 0.0), \
        "active party must send NOTHING on the uplink"
    np.testing.assert_array_equal(np.asarray(up[1:]),
                                  np.asarray(E_raw[1:] + full[1:]))
    np.testing.assert_allclose(np.asarray(masks).sum(0), 0.0, atol=1e-4)
    for k in range(1, ss.C):
        delta = np.abs(np.asarray(up[k]) - np.asarray(E_raw[k]))
        assert delta.max() > 0.5, \
            f"party {k} raw embedding visible on the party collective"


# ---------------------------------------------------------------------------
# LLM scale: sharded == vectorized (train + serve/prefill transcripts)
# ---------------------------------------------------------------------------


def _lm(engine):
    cfg = smoke_variant(get_config("qwen2.5-3b"))
    e = EasterConfig(num_passive=4, d_embed=64, decision_layers=1)
    return EasterLM(cfg=cfg, easter=e, engine=engine)


def test_lm_sharded_loss_bitexact():
    sv, ss = _lm("vectorized"), _lm("sharded")
    assert ss._shard_ok()
    params = sv.init_params(jax.random.PRNGKey(12))
    key = jax.random.PRNGKey(13)
    V = sv.cfg.vocab_size
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, V),
             "labels": jax.random.randint(jax.random.fold_in(key, 1),
                                          (2, 16), 0, V)}
    for seeds_v, seeds_s in ((sv.mask_seeds(), ss.mask_seeds()),
                             (None, None)):
        lv, pv = sv.loss_fn(params, batch, 0, seeds_v)
        ls, ps = ss.loss_fn(params, batch, 0, seeds_s)
        np.testing.assert_array_equal(np.asarray(lv), np.asarray(ls))
        np.testing.assert_array_equal(np.asarray(pv), np.asarray(ps))
    gv = jax.grad(lambda p: sv.loss_fn(p, batch, 0, sv.mask_seeds())[0])(
        params)
    gs = jax.grad(lambda p: ss.loss_fn(p, batch, 0, ss.mask_seeds())[0])(
        params)
    _grads_close(gv, gs)


@pytest.mark.parametrize("engine", ["vectorized", "sharded"])
def test_lm_serve_prefill_matches_loop_bitexact(engine):
    """The grouped decode/prefill paths (one vmap over the stacked passive
    proxies; in-shard blinding under the sharded engine) must reproduce
    the per-party loop oracle's transcripts bit-for-bit — blinded and
    unblinded."""
    sl, sn = _lm("loop"), _lm(engine)
    params = sl.init_params(jax.random.PRNGKey(14))
    B_, S = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(15), (B_, S), 0,
                              sl.cfg.vocab_size)
    pos = jnp.asarray(S - 1, jnp.int32)
    for blinded in (True, False):
        sd_l = sl.mask_seeds() if blinded else None
        sd_n = sn.mask_seeds() if blinded else None
        c_l, c_n = sl.init_caches(B_, S), sn.init_caches(B_, S)
        E_l, c_l = sl.prefill(params, toks[:, :S - 1], c_l, seeds=sd_l,
                              round_idx=3)
        E_n, c_n = sn.prefill(params, toks[:, :S - 1], c_n, seeds=sd_n,
                              round_idx=3)
        np.testing.assert_array_equal(np.asarray(E_l), np.asarray(E_n))
        lg_l, c_l = sl.serve_step(params, toks[:, S - 1:], c_l, pos, sd_l)
        lg_n, c_n = sn.serve_step(params, toks[:, S - 1:], c_n, pos, sd_n)
        np.testing.assert_array_equal(np.asarray(lg_l), np.asarray(lg_n))
        # caches agree too (same pytree layout, same values)
        for a, b in zip(jax.tree.leaves(c_l), jax.tree.leaves(c_n)):
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_lm_sharded_non_divisible_k_falls_back():
    """num_passive=3 doesn't divide the 4-way axis: engine="sharded" must
    degrade to the vectorized path, not crash or skew."""
    cfg = smoke_variant(get_config("qwen2.5-3b"))
    e = EasterConfig(num_passive=3, d_embed=64, decision_layers=1)
    sv = EasterLM(cfg=cfg, easter=e)
    ss = EasterLM(cfg=cfg, easter=e, engine="sharded")
    assert not ss._shard_ok()
    params = sv.init_params(jax.random.PRNGKey(16))
    key = jax.random.PRNGKey(17)
    batch = {"tokens": jax.random.randint(key, (2, 8), 0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.fold_in(key, 1),
                                          (2, 8), 0, cfg.vocab_size)}
    lv, _ = sv.loss_fn(params, batch, 0, sv.mask_seeds())
    ls, _ = ss.loss_fn(params, batch, 0, ss.mask_seeds())
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(ls))
