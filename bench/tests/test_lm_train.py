"""The language-model training cell's runner at small widths on the CPU."""
import time

from bench import harness
from conftest import make_root, tiny_lm_train_cell


def test_tiny_lm_train_cell_runs_and_is_correct(tmp_path, tiny_lm_arch):
    root = make_root(tmp_path, [tiny_lm_train_cell()])
    res = harness.run_cell(
        ["--workload", "tiny-lm.train", "--seed", "2147483651",
         "--seconds", "0.5", "--trace", "0"], root=root,
        t0=time.perf_counter(), require_chip=False)
    print(res["checks"])
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "train_tokens_per_s"}


import pytest  # noqa: E402


@pytest.mark.parametrize("mode", ["control", "half_batch", "unchanged"])
def test_lm_control_and_faults_fail_the_limits(tmp_path, tiny_lm_arch, mode):
    from bench import control
    root = make_root(tmp_path, [tiny_lm_train_cell()])
    line, = control.readings(root, "tiny-lm.train", [5], [mode], 0.5,
                             require_chip=False)
    print(line)
    limits = harness.load_json(f"{root}/bench/limits/tiny-lm.train.json")
    assert any(line[k] > v for k, v in limits.items()), line
