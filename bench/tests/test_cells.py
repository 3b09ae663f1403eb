"""A run of a small cell, driven end to end on the CPU, and the faults that
its comparison has to catch."""
import time

import pytest

from bench import harness

ARGS = ["--workload", "mlp-c8.train", "--seed", "3000000007", "--seconds",
        "0.5", "--trace", "0"]


def run(root):
    return harness.run_cell(ARGS, root=root, t0=time.perf_counter(),
                            require_chip=False)


def test_new_cell_from_new_files_runs_and_is_correct(tiny_root):
    res = run(tiny_root)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "round_ms"}
    assert list(res)[-1] == "checks"


def _patch_step(monkeypatch, wrap):
    from repro.core.protocol import EasterClassifier
    orig = EasterClassifier.make_train_step

    def make(self, *a, **kw):
        init_opt, step = orig(self, *a, **kw)
        return init_opt, wrap(step)

    monkeypatch.setattr(EasterClassifier, "make_train_step", make)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tiny_root, monkeypatch):
    def wrap(step):
        def frozen(params, opt, xs, y, masks):
            _, _, total, per = step(params, opt, xs, y, masks)
            return params, opt, total, per
        return frozen

    _patch_step(monkeypatch, wrap)
    assert not run(tiny_root)["correct"]


def test_half_of_the_batch_left_out_is_not_correct(tiny_root, monkeypatch):
    def wrap(step):
        def half(params, opt, xs, y, masks):
            h = y.shape[0] // 2
            m = None if masks is None else masks[:, :h]
            return step(params, opt, [x[:h] for x in xs], y[:h], m)
        return half

    _patch_step(monkeypatch, wrap)
    assert not run(tiny_root)["correct"]


@pytest.mark.parametrize("mode", ["control", "half_batch", "unchanged"])
def test_control_and_faults_fail_the_limits(tiny_root, mode):
    """The reference in bfloat16, or with a fault planted, in the
    program's place: at least one number is over its limit."""
    from bench import control
    line, = control.readings(tiny_root, "mlp-c8.train", [11], [mode], 0.5,
                             require_chip=False)
    limits = harness.load_json(f"{tiny_root}/bench/limits/mlp-c8.train.json")
    assert any(line[k] > v for k, v in limits.items()), line


def test_off_a_tpu_the_run_exits_non_zero_and_names_the_platform(
        tiny_root, capsys):
    rc = harness.main(ARGS, root=tiny_root, t0=time.perf_counter())
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "cpu" in out.err
