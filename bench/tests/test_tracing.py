"""The reduction from a profiler trace to per-layer numbers.

``data/small.xplane.pb`` was recorded on a TPU v5 lite by
``record_trace.py``: four rounds of a 512 x 512 matrix product and an
elementwise pass, waited for one by one, with 2 ms host sleeps between.
"""
import os

import pytest

from bench import flops, tracing

DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_interval_arithmetic_by_hand():
    assert tracing.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tracing.subtract([(0, 10)], [(1, 2), (4, 5), (9, 12)]) == [
        (0, 1), (2, 4), (5, 9)]
    assert tracing.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert tracing.clip([(0, 2), (4, 9)], 1, 5) == [(1, 2), (4, 5)]
    own = dict((ev[0], t) for ev, t in tracing.exclusive(
        [("loop", 0.0, 10.0), ("a", 1.0, 3.0), ("b", 4.0, 5.0),
         ("c", 12.0, 13.0)]))
    assert own == {"loop": 7.0, "a": 2.0, "b": 1.0, "c": 1.0}


def test_collective_exposed_by_hand():
    d = tracing.DeviceTrace(ops=[("all-gather.1", 0.0, 4.0),
                                 ("fusion.2", 1.0, 2.0),
                                 ("fusion.3", 3.0, 6.0)])
    s = tracing.TraceSummary(window=(0.0, 10.0), devices=[d],
                             host_spans=[])
    assert s.collective_seconds() == (4.0, 2.0)
    assert s.busy_s == 6.0
    assert s.idle_share == pytest.approx(0.4)


@pytest.fixture(scope="module")
def recorded():
    return tracing.reduce(DATA, n_devices=1)


def test_recorded_trace_busy_union_and_programs(recorded):
    """Busy time and per-program time, against the events read directly."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(DATA)
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = next(l for l in plane.lines if l.name == "XLA Ops")
    lo, hi = recorded.window
    ivs = sorted((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                 for e in ops.events)
    busy, end = 0.0, lo
    for s, e in ivs:
        s, e = max(s, end), min(e, hi)
        if e > s:
            busy += e - s
            end = e
    assert recorded.busy_s == pytest.approx(busy, rel=1e-9)
    assert 0 < recorded.busy_s < recorded.window_s
    # both programs are lambdas: one name, jit__lambda; a program's span
    # covers its operations
    progs = recorded.program_seconds()
    assert set(progs) == {"jit__lambda"}
    assert busy <= progs["jit__lambda"] <= recorded.window_s


def test_recorded_trace_idle_gaps_name_the_host_sleep(recorded):
    gaps = recorded.idle_gaps()
    assert gaps[0][0] == "bench.sleep"
    assert gaps[0][1] >= 0.002
    b = recorded.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] > 0


def test_classifier_flops_by_hand():
    cfg = {"parties": 2, "n_classes": 3, "d_embed": 4,
           "features": {"n_features": 5},
           "party_mlps": [{"embed": [6], "decide": [7]}]}
    # party 0: 3 features, party 1: 2; embed (nf->6->4), decide (4->7->3)
    fwd = 2 * 10 * (3 * 6 + 6 * 4 + 4 * 7 + 7 * 3) \
        + 2 * 10 * (2 * 6 + 6 * 4 + 4 * 7 + 7 * 3)
    assert flops.classifier_round(cfg, 10) == 3 * fwd


def test_lm_flops_and_bytes_by_hand():
    m = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 4, "vocab_size": 10,
         "num_hidden_layers": 4, "torch_dtype": "bfloat16",
         "easter": {"num_passive": 1, "d_embed": 4, "decision_layers": 1,
                    "passive_depth_frac": 0.5}}
    # attention for 1 token at context 3: q,k,v,o projections + QK, PV
    attn = 2 * 8 * 4 * (2 + 2) + 2 * 2 * 4 * 8 + 2 * 2 * 3 * 2 * 4
    mlp = 2 * 8 * 16 * 3
    embed = lambda n: n * (attn + mlp) + 2 * 8 * 4  # noqa: E731
    decide = 2 * 4 * 16 * 3 + 2 * 4 * 10
    assert flops.lm_decode_token(m, 3) == embed(4) + embed(2) + decide
    # a decode round reads: per layer attention with q/k/v biases, SwiGLU,
    # two norms; per party the final norm and the projection; the active
    # party's decision block, final norm and head; no embedding table
    block = 8 * 4 * 4 + 4 * 4 + 2 * 4 * 8 + 3 * 8 * 16 + 2 * 8
    decide = (3 * 4 * 16 + 4) + 4 + 4 * 10
    n = (4 * block + 8 + 8 * 4) + (2 * block + 8 + 8 * 4) + decide
    assert flops.lm_decode_weight_bytes(m) == 2 * n
    assert flops.lm_embed_row_bytes(m) == 2 * 8 * 2
    assert flops.lm_kv_bytes_per_token(m) == (4 + 2) * 2 * 1 * 4 * 2
    ops, nbytes = flops.blind_agg(4, 2, 3)
    assert ops == 6 * 7 and nbytes == 4 * 6 * 8


def test_program_time_is_the_busiest_chips():
    """A program placed on one chip of two counts in full."""
    d0 = tracing.DeviceTrace(modules=[("jit_masks(1)", 0.0, 2.0),
                                      ("jit_step(2)", 2.0, 3.0)])
    d1 = tracing.DeviceTrace(modules=[("jit_step(2)", 2.0, 3.5)])
    s = tracing.TraceSummary(window=(0.0, 10.0), devices=[d0, d1],
                             host_spans=[])
    assert s.program_seconds() == {"jit_masks": 2.0, "jit_step": 1.5}


def test_decode_bytes_match_the_programs_parameters(tiny_lm_arch):
    """The weights a decode round reads, counted from the program's own
    parameter shapes: every leaf but the token tables, and of the passive
    parties neither decision net nor head."""
    import jax
    import numpy as np
    from bench import lm_system
    from conftest import tiny_lm_config

    class Ctx:
        config = tiny_lm_config()

    _, shapes = lm_system.build(Ctx)
    n = 0
    for k, party in enumerate(shapes["parties"]):
        for path, leaf in jax.tree_util.tree_flatten_with_path(party)[0]:
            top = str(path[0].key)
            if "embed" in jax.tree_util.keystr(path):
                continue
            if k > 0 and top in ("decision", "final_norm", "head"):
                continue
            n += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    assert flops.lm_decode_weight_bytes(Ctx.config) == n


def test_mask_ms_reads_the_mask_programs_or_nothing():
    """The eager mask programs are summed per round; where no random-number
    program runs outside the step the metric is left out, not read 0."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "mask_ms", os.path.join(os.path.dirname(os.path.dirname(__file__)),
                                "metrics", "mask_ms.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    def view(modules):
        d = tracing.DeviceTrace(modules=modules)
        s = tracing.TraceSummary(window=(0.0, 10.0), devices=[d],
                                 host_spans=[])
        return type("Run", (), {"trace": s, "counters": {"rounds": 2}})

    eager = [("jit__normal(1)", 0.0, 3.0), ("jit_multiply(2)", 3.0, 4.0),
             ("jit_scan(3)", 4.0, 4.5), ("jit_train_step(4)", 5.0, 9.0)]
    assert reader.read(view(eager)) == pytest.approx(1e3 * 4.5 / 2)
    fused = [("jit_train_step(4)", 5.0, 9.0), ("jit_multiply(2)", 3.0, 4.0)]
    assert reader.read(view(fused)) is None
