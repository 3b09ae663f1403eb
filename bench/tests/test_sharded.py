"""The classifier cell laid over four (host) devices, and the fault only
such a cell can have: the exchange between chips left out."""
import time

import pytest

from bench import harness
from conftest import make_root, tiny_sharded_cell

ARGS = ["--workload", "mlp-c16.train-4chip", "--seed", "2147483999",
        "--seconds", "0.5", "--trace", "0"]


@pytest.fixture(scope="module")
def sharded_root(tmp_path_factory):
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four host devices (XLA_FLAGS)")
    return make_root(tmp_path_factory.mktemp("sharded"),
                     [tiny_sharded_cell()])


def test_sharded_cell_runs_and_is_correct(sharded_root):
    res = harness.run_cell(ARGS, root=sharded_root, t0=time.perf_counter(),
                           require_chip=False)
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4


def test_exchange_left_out_is_not_correct(sharded_root):
    from bench import control
    line, = control.readings(sharded_root, "mlp-c16.train-4chip", [3],
                             ["no_exchange"], 0.5, require_chip=False)
    limits = harness.load_json(
        f"{sharded_root}/bench/limits/mlp-c16.train-4chip.json")
    assert any(line[k] > v for k, v in limits.items()), line
