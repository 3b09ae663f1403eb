"""The serving cell's runner at small widths on the CPU, and the fault its
comparison has to catch: a token altered where it is produced."""
import time

import pytest

from bench import harness
from conftest import make_root, tiny_lm_serve_cell

ARGS = ["--workload", "tiny-lm.serve", "--seed", "2147483700",
        "--seconds", "1", "--trace", "0"]


@pytest.fixture
def serve_root(tmp_path, tiny_lm_arch):
    return make_root(tmp_path, [tiny_lm_serve_cell()])


def run(root):
    return harness.run_cell(ARGS, root=root, t0=time.perf_counter(),
                            require_chip=False)


def test_tiny_serve_cell_runs_and_is_correct(serve_root):
    res = run(serve_root)
    print(res["metrics"], res["checks"], res["attempted"])
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                   "ttft_p95_ms", "tpot_p95_ms"}


def test_a_served_token_altered_is_not_correct(serve_root, monkeypatch):
    from repro.core import api
    orig = api.build_decoder

    def build(sys, cfg):
        prefill, decode = orig(sys, cfg)

        def altered(params, state):
            buf, state, steps = decode(params, state)
            return buf.at[:, 0].set((buf[:, 0] + 1) % 256), state, steps

        return prefill, altered

    monkeypatch.setattr(api, "build_decoder", build)
    assert not run(serve_root)["correct"]


def test_the_float8_control_fails_the_limit(serve_root):
    from bench import control
    line, = control.readings(serve_root, "tiny-lm.serve", [7], ["control"],
                             1.0, require_chip=False)
    print(line)
    limits = harness.load_json(f"{serve_root}/bench/limits/tiny-lm.serve.json")
    assert line["logit_gap"] > limits["logit_gap"] >= line["sound_logit_gap"]


@pytest.mark.parametrize("leaf", ["b", "scale"])
def test_a_program_that_drops_biases_or_norm_scales_is_not_correct(
        serve_root, monkeypatch, leaf):
    """The served weights carry drawn q/k/v biases and norm scales; a
    program that serves without them (biases zero, scales one) is caught."""
    import jax
    import jax.numpy as jnp
    from repro.core import serving
    orig = serving.ServingEngine.__init__

    def drop(path, a):
        if str(getattr(path[-1], "key", "")) != leaf:
            return a
        return jnp.zeros_like(a) if leaf == "b" else jnp.ones_like(a)

    def init(self, system, params, **kw):
        orig(self, system, jax.tree_util.tree_map_with_path(drop, params),
             **kw)

    monkeypatch.setattr(serving.ServingEngine, "__init__", init)
    res = run(serve_root)
    print(res["checks"])
    assert not res["correct"], res["checks"]
