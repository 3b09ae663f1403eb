"""Parameters and optimizer state carried across the jitted train step as
per-group stacks (``party_engine.StackedParties``).

The grouped engines' step takes and returns one stack per (execution
group, optimizer) subgroup instead of C per-party pytrees, so a call hands
over O(#groups) arrays. The stacks must still read, party by party, as the
plain per-party list did; a plain list fed to the step is stacked once
(``train.restacks``); and the carried step must match the loop-engine
oracle. Run on the C=64 MLP zoo's structure at a small batch, on one
device and laid out over a 4-way party axis.
"""
import os

import numpy as np
import pytest

N_DEV = 4
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={N_DEV}"

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import obs                                        # noqa: E402
from repro.configs.base import EasterConfig                  # noqa: E402
from repro.core.party_engine import StackedParties           # noqa: E402
from repro.core.party_models import PartyArch                # noqa: E402
from repro.core.protocol import EasterClassifier, split_features  # noqa
from repro.optim import resolve_party_optimizers             # noqa: E402

# the C=64 zoo: four MLP shapes (10, 8, 10 and 10 leaves) cycled over 64
# parties, 784 features in 64 slices 13 or 12 wide -> 8 execution groups
# of [4, 4, 4, 4, 12, 12, 12, 12] parties
MLPS = [((256, 128), (128,)), ((128,), (64,)), ((512, 256), (256,)),
        ((192, 96), (96,))]
C, N_FEATURES, D_EMBED, N_CLS, B, ROUNDS = 64, 784, 128, 10, 8, 3
PARAM_STACK_LEAVES = 10 + 8 + 10 + 10 + 10 + 8 + 10 + 10


def _system(engine):
    arches = [PartyArch("mlp", *MLPS[k % 4], D_EMBED, N_CLS)
              for k in range(C)]
    nf = [s.shape[-1] for s in split_features(jnp.zeros((1, N_FEATURES)), C)]
    return EasterClassifier(EasterConfig(num_passive=C - 1, d_embed=D_EMBED),
                            arches, nf, engine=engine)


def _rounds(sys_, step, params, opt, data, feed=lambda p, s: (p, s)):
    losses = []
    for r, (xs, y) in enumerate(data):
        params, opt = feed(params, opt)
        params, opt, _, per = step(params, opt, xs, y, sys_.masks(B, r))
        losses.append(per)
    return params, opt, jnp.stack(losses)


def _assert_party_equal(stacked, plain, **tol):
    """Party k of ``stacked`` has party k's structure and values."""
    assert len(stacked) == len(plain)
    for a_k, b_k in zip(stacked, plain):
        assert jax.tree.structure(a_k) == jax.tree.structure(b_k)
        for a, b in zip(jax.tree.leaves(a_k), jax.tree.leaves(b_k)):
            if tol:
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           **tol)
            else:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(scope="module")
def zoo():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (ROUNDS, B, N_FEATURES))
    ys = jax.random.randint(jax.random.fold_in(key, 1), (ROUNDS, B), 0, N_CLS)
    data = [(split_features(x[r], C), ys[r]) for r in range(ROUNDS)]
    # drawn on the host: C=64 parties' init compiles for longer than the
    # steps under test
    rng = np.random.default_rng(1)
    params = jax.tree.map(
        lambda s: jnp.asarray(rng.normal(0.0, 0.1, s.shape), s.dtype),
        jax.eval_shape(_system("loop").init_params, jax.random.PRNGKey(1)))
    return params, data


@pytest.fixture(scope="module")
def loop_run(zoo):
    params, data = zoo
    sys_ = _system("loop")
    init_opt, step = sys_.make_train_step("adam", 1e-3)
    return _rounds(sys_, step, params, init_opt(params), data)


@pytest.fixture(scope="module", params=["vectorized", "sharded"])
def carried(request, zoo, tmp_path_factory):
    """The engine's step over ROUNDS rounds, the state fed back as it comes
    out, with ``train.restacks`` read from a trace around all of it."""
    if request.param == "sharded" and jax.device_count() < N_DEV:
        pytest.skip("requires a multi-device host")
    params, data = zoo
    sys_ = _system(request.param)
    init_opt, step = sys_.make_train_step("adam", 1e-3)
    opt = init_opt(params)
    obs.take()
    restacks, losses = [], []
    with jax.profiler.trace(str(tmp_path_factory.mktemp("trace"))):
        for r, (xs, y) in enumerate(data):
            params, opt, _, per = step(params, opt, xs, y, sys_.masks(B, r))
            restacks.append(obs.take()[1].get("train.restacks", 0))
            losses.append(per)
        jax.block_until_ready(params)
    return sys_, step, params, opt, jnp.stack(losses), restacks


def test_stacks_index_iterate_and_len_like_the_plain_list(zoo):
    params, _ = zoo
    sys_ = _system("vectorized")
    opts = resolve_party_optimizers({}, C, default=("adam", 1e-3, None))
    stacked = StackedParties.stack(params, sys_._eng.subgroups(opts))
    assert isinstance(stacked, StackedParties)
    assert len(stacked.stacks) == sys_._eng.n_groups == 8
    assert len(jax.tree.leaves(stacked)) == PARAM_STACK_LEAVES
    _assert_party_equal(stacked, params)
    _assert_party_equal(list(stacked), params)
    _assert_party_equal([stacked[-1]], [params[-1]])
    with pytest.raises(IndexError):
        stacked[C]


def test_step_carries_the_groups_stacks(carried):
    """The compiled step's params argument is the groups' stacks (76
    leaves for the zoo), not 64 parties' 608 leaves; its output is the same
    stacks, with the state stacked the same way (one Adam count a group)."""
    sys_, step, params, opt, _, _ = carried
    assert isinstance(params, StackedParties)
    assert isinstance(opt, StackedParties)
    assert params.members == opt.members
    assert len(jax.tree.leaves(params)) == PARAM_STACK_LEAVES
    assert len(jax.tree.leaves(opt)) == 2 * PARAM_STACK_LEAVES + 8
    xs, y = [jnp.zeros((B, n)) for n in sys_.n_features], jnp.zeros(
        (B,), jnp.int32)
    lowered = step.__wrapped__.lower(params, opt, xs, y, sys_.masks(B, 0))
    args, _ = lowered.args_info
    assert len(jax.tree.leaves(args[0])) == PARAM_STACK_LEAVES


def test_restacks_once_then_never(carried):
    """Only the first call, fed the plain list, stacks: once."""
    assert carried[-1] == [1, 0, 0]


def test_fed_back_stacks_equal_plain_lists_fed_each_round(carried, zoo):
    sys_, step, params, opt, losses, _ = carried
    p0, data = zoo
    init_opt, _ = sys_.make_train_step("adam", 1e-3)
    p_l, opt_l, losses_l = _rounds(
        sys_, step, p0, list(init_opt(p0)), data,
        feed=lambda p, s: jax.device_get((list(p), list(s))))
    np.testing.assert_array_equal(np.asarray(losses), np.asarray(losses_l))
    _assert_party_equal(params, list(p_l))
    _assert_party_equal(opt, list(opt_l))


def test_carried_step_matches_loop_engine_party_by_party(carried, loop_run):
    _, _, params, opt, losses, _ = carried
    p_l, opt_l, losses_l = loop_run
    np.testing.assert_allclose(np.asarray(losses), np.asarray(losses_l),
                               rtol=1e-5)
    _assert_party_equal(params, p_l, rtol=3e-6, atol=1e-7)
    _assert_party_equal(opt, opt_l, rtol=3e-6, atol=1e-7)


def test_stacks_keep_their_layout_across_the_step(carried):
    """On the mesh each group's stack keeps its parties on their own
    devices across the call boundary, state included; without one the
    stacks stay on the one device."""
    sys_, _, params, opt, _, _ = carried
    if sys_.engine == "sharded":
        want = NamedSharding(sys_.mesh, P("party"))
    else:
        want = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    for leaf in jax.tree.leaves((params, opt)):
        assert leaf.sharding.is_equivalent_to(want, leaf.ndim)
