"""The readers of the program's own spans and counters, on fabricated runs,
and on traced runs of the small cells on the CPU."""
import os
import time

import pytest

from bench import harness, program_spans, tracing
from conftest import make_root, tiny_lm_serve_cell

S = 1_000_000_000                     # nanoseconds a second


def span(name, start_s, end_s, ids=None, id=0, parent=None):
    from repro.obs import Span
    return Span(name, id, parent, round(start_s * S), round(end_s * S),
                ids or {})


def view(spans, counters, *, host=(), ops=(), window=(0.0, 100.0)):
    """A RunView whose program kept ``spans`` and ``counters``."""
    trace = tracing.TraceSummary(
        window=window, host_spans=list(host),
        devices=[tracing.DeviceTrace(ops=list(ops))] if ops else [])
    run = harness.RunView(peaks={}, kind="", chips=1, window_s=window[1]
                          - window[0], counters={}, trace=trace)
    program_spans._last = (run, (spans, counters))
    return run


METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def reader(name):
    return harness.load_module(os.path.join(METRICS, name + ".py"), name)


@pytest.fixture(autouse=True)
def _forget_records():
    yield
    program_spans._last = (None, None)


def test_classifier_readers_by_hand():
    spans = [span("masks", 0.0, 0.030), span("train.step", 0.030, 0.110),
             span("masks", 0.2, 0.234), span("train.step", 0.234, 0.324)]
    run = view(spans, {"compiles.masks": 4})
    assert reader("mask_dispatch_ms").read(run) == pytest.approx(32.0)
    assert reader("step_dispatch_ms").read(run) == pytest.approx(85.0)
    assert reader("mask_compiles_per_round").read(run) == 2.0
    # masks that compile nothing read 0, not nothing
    run = view(spans, {})
    assert reader("mask_compiles_per_round").read(run) == 0.0


def test_readers_read_nothing_without_program_records():
    run = view([], {})
    for name in ("step_dispatch_ms", "mask_dispatch_ms",
                 "mask_compiles_per_round", "decode_lane_use",
                 "first_token_wait_ms", "engine_idle_ms"):
        assert reader(name).read(run) is None, name
    program_spans._last = (run, None)          # a program with no obs
    for name in ("step_dispatch_ms", "decode_lane_use", "engine_idle_ms"):
        assert reader(name).read(run) is None, name


def test_lane_use_and_first_token_wait_by_hand():
    reqs = [span("serve.request", 0.0, 1.0,
                 {"nonce": k, "t_admit": 0.1, "t_first": 0.1 + 0.01 * k})
            for k in range(1, 21)]
    reqs.append(span("serve.request", 0.0, 1.0, {"nonce": 0, "t_admit": 0.1,
                                                 "t_first": None}))
    # admitted in the window: a prefill span for each of nonces 0..20
    admitted = [span("serve.prefill", 0.1, 0.105, {"nonce": k})
                for k in range(21)]
    # admitted before the window (no prefill span), finished in it
    early = [span("serve.request", 0.0, 1.0,
                  {"nonce": 100 + k, "t_admit": 0.0, "t_first": 0.6})
             for k in range(5)]
    run = view(reqs + admitted + early,
               {"serve.tokens": 90, "serve.lane_slots": 120})
    assert reader("decode_lane_use").read(run) == pytest.approx(75.0)
    # numpy's linear percentile of 10, 20, ..., 200 ms; the early
    # requests' 600 ms waits are not among them
    assert reader("first_token_wait_ms").read(run) == pytest.approx(190.5)
    # no request admitted in the window: nothing to read
    run = view(reqs + early, {})
    assert reader("first_token_wait_ms").read(run) is None


def steps(starts, skew=0.0, dur=0.004):
    """Program ``serve.step`` spans on a perf_counter clock 500 s behind
    the trace, and the runner's ``bench.step`` around each (5 us before)."""
    prog = [span("serve.step", 500.0 + t + skew * k, 500.0 + t + dur)
            for k, t in enumerate(starts)]
    host = [("bench.window", 0.0, 100.0)] + [
        ("bench.step", 1000.0 + t - 5e-6, 1000.0 + t + dur + 1e-5)
        for t in starts]
    return prog, host


def test_clock_offset_pairs_runner_and_program_steps():
    prog, host = steps([0.0, 0.01, 0.02, 0.03])
    run = view(prog, {}, host=host, window=(990.0, 1010.0))
    off = program_spans.trace_offset_s(run, prog, "serve.step")
    assert off == pytest.approx(500.0 - 5e-6, abs=1e-9)


def test_clock_offset_refuses_a_count_mismatch_or_a_spread():
    prog, host = steps([0.0, 0.01, 0.02, 0.03])
    run = view(prog[:3], {}, host=host, window=(990.0, 1010.0))
    assert program_spans.trace_offset_s(run, prog[:3], "serve.step") is None
    # a runner span outside the window is not paired
    run = view(prog[:3], {}, host=host, window=(990.0, 1000.025))
    assert program_spans.trace_offset_s(run, prog[:3], "serve.step") \
        is not None
    prog, host = steps([0.0, 0.01, 0.02, 0.03, 0.04], skew=40e-6)
    run = view(prog, {}, host=host, window=(990.0, 1010.0))
    assert program_spans.trace_offset_s(run, prog, "serve.step") is None
    prog, host = steps([0.0, 0.01, 0.02, 0.03, 0.04], skew=10e-6)
    run = view(prog, {}, host=host, window=(990.0, 1010.0))
    assert program_spans.trace_offset_s(run, prog, "serve.step") is not None


def test_engine_idle_counts_idle_under_the_engine_only():
    # two engine steps of 4 ms on the trace clock at 1000.00 and 1000.01;
    # the chip is busy 1000.001-1000.003 and 1000.006-1000.012
    prog, host = steps([0.0, 0.01])
    ops = [("fusion", 1000.001, 1000.003), ("fusion", 1000.006, 1000.012)]
    win = (999.999, 1000.02)
    run = view(prog, {"serve.chunks": 2}, host=host, ops=ops, window=win)
    # the steps land where the runner's spans start, 5 us early; idle
    # under them: [-.000005, .001] + [.003, .003995] + [.012, .013995]
    assert reader("engine_idle_ms").read(run) == pytest.approx(3.995 / 2)
    run = view(prog[:1], {"serve.chunks": 2}, host=host, ops=ops, window=win)
    assert reader("engine_idle_ms").read(run) is None
    run = view(prog, {"serve.chunks": 2}, host=host, window=win)
    assert reader("engine_idle_ms").read(run) is None        # no chip


def test_records_are_taken_once_per_run():
    from repro import obs
    obs.take()
    a = harness.RunView({}, "", 1, 1.0, {}, None)
    first = program_spans.records(a)
    assert first == ([], {})
    assert program_spans.records(a) is first


TRACED = ["--seed", "3000000011", "--seconds", "1", "--trace", "1"]


def test_traced_classifier_run_reports_the_program_spans(tiny_root):
    res = harness.run_cell(["--workload", "mlp-c8.train"] + TRACED,
                           root=tiny_root, t0=time.perf_counter(),
                           require_chip=False)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert {"step_dispatch_ms", "mask_dispatch_ms",
            "mask_compiles_per_round"} <= set(m), m
    rounds = res["attempted"]
    assert m["step_dispatch_ms"] + m["mask_dispatch_ms"] \
        <= 1e3 * res["window_s"] / rounds


def test_traced_serve_run_reports_the_program_spans(tmp_path, tiny_lm_arch):
    root = make_root(tmp_path, [tiny_lm_serve_cell()])
    res = harness.run_cell(["--workload", "tiny-lm.serve"] + TRACED,
                           root=root, t0=time.perf_counter(),
                           require_chip=False)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert {"decode_lane_use", "first_token_wait_ms"} <= set(m), m
    assert 0 < m["decode_lane_use"] <= 100
    assert m["first_token_wait_ms"] > 0
    assert "engine_idle_ms" not in m          # no chip in a CPU trace
