"""Program spans and counters (``repro.obs``) and the layer boundaries that
emit them: nothing is kept without a profiler trace; inside one, spans
nest, compiles are charged to the innermost span, the spans land in the
written trace, and the serving engine's counters and first-token times
agree with what it served."""
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import EasterConfig, get_config, smoke_variant
from repro.core import api, serving
from repro.core.easter_lm import EasterLM
from repro.core.party_models import PartyArch
from repro.core.protocol import EasterClassifier


def _names(spans):
    return [s.name for s in spans]


def test_without_a_trace_nothing_is_kept():
    obs.take()
    assert obs.span("a") is obs.span("b", lane=3)
    with obs.span("a"):
        obs.count("n", 5)
        obs.interval("r", 0.0, 1.0, nonce=1)
        jax.jit(lambda x: x * 7 - 2)(jnp.ones(3)).block_until_ready()
    assert obs.take() == ([], {})


def test_a_span_without_a_trace_costs_under_a_microsecond():
    n, best = 1000, float("inf")
    for _ in range(50):
        t = time.perf_counter()
        for _ in range(n):
            with obs.span("x", lane=1):
                pass
        best = min(best, (time.perf_counter() - t) / n)
    assert best < 1e-6, best


def test_spans_nest_count_compiles_and_land_in_the_trace(tmp_path):
    obs.take()
    fresh = jax.jit(lambda x: jnp.sin(x) * 3.5 + 0.25)
    x = jnp.arange(5.0)
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("outer", k=1):
            with obs.span("inner"):
                fresh(x).block_until_ready()
            obs.count("things", 2)
            obs.count("things")
        obs.interval("life", 1.5, 2.0, nonce=7)
    spans, counters = obs.take()
    by = {s.name: s for s in spans}
    assert set(by) == {"outer", "inner", "life"}
    assert by["outer"].parent_id is None and by["outer"].ids == {"k": 1}
    assert by["inner"].parent_id == by["outer"].id
    assert by["outer"].start_ns <= by["inner"].start_ns \
        <= by["inner"].end_ns <= by["outer"].end_ns
    assert (by["life"].start_ns, by["life"].end_ns) == (1_500_000_000,
                                                        2_000_000_000)
    assert by["life"].ids == {"nonce": 7}
    assert counters["things"] == 3
    assert counters["compiles.inner"] == 1       # one program compiled
    assert "compiles.outer" not in counters
    assert obs.take() == ([], {})

    from jax.profiler import ProfileData
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {ev.name: dict(ev.stats) for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for line in p.lines
            for ev in line.events}
    assert {"easter.outer", "easter.inner"} <= set(host)
    assert host["easter.outer"].get("k") == 1    # ids reach the trace


def test_a_program_loaded_from_the_persistent_cache_counts_once(tmp_path):
    from jax._src import compilation_cache
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    x = jnp.arange(6.0)
    try:
        jax.config.update("jax_compilation_cache_dir",
                          str(tmp_path / "cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        counts = []
        for _ in range(2):               # a miss that compiles, then a hit
            jax.clear_caches()
            obs.take()
            with jax.profiler.trace(str(tmp_path / "trace")):
                with obs.span("f"):
                    jax.jit(lambda v: jnp.cos(v) * 1.75 - 0.5)(
                        x).block_until_ready()
            counts.append(obs.take()[1].get("compiles.f"))
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert counts == [1, 1]


@pytest.fixture(scope="module")
def classifier():
    C, nf = 4, [5, 4, 4, 3]
    arches = [PartyArch("mlp", (8,), (8,), 8, 3) for _ in range(C)]
    sys_ = EasterClassifier(EasterConfig(num_passive=C - 1, d_embed=8),
                            arches, nf)
    params = sys_.init_params(jax.random.PRNGKey(0))
    key = jax.random.split(jax.random.PRNGKey(1), C + 1)
    xs = [jax.random.normal(key[k], (16, n)) for k, n in enumerate(nf)]
    y = jax.random.randint(key[C], (16,), 0, 3)
    return sys_, params, xs, y


def test_wrapped_train_step_matches_the_jitted_step(classifier, tmp_path):
    sys_, params, xs, y = classifier
    init_opt, step = sys_.make_train_step("adam", 1e-2)
    opt = init_opt(params)
    masks = sys_.masks(16, 0)
    ref = step.__wrapped__(params, opt, xs, y, masks)
    obs.take()
    with jax.profiler.trace(str(tmp_path)):
        masks = sys_.masks(16, 0)
        got = step(params, opt, xs, y, masks)
        jax.block_until_ready(got)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert step.__name__ == "step"
    spans, _ = obs.take()
    assert _names(spans) == ["masks", "train.step"]


def _lm():
    cfg = smoke_variant(get_config("qwen2.5-3b"))
    return EasterLM(cfg=cfg, easter=EasterConfig(num_passive=2, d_embed=32,
                                                 decision_layers=1),
                    engine="vectorized")


@pytest.fixture(scope="module")
def lm():
    sys_ = _lm()
    return sys_, sys_.init_params(jax.random.PRNGKey(0))


def test_serving_engine_counts_what_it_served(lm, tmp_path):
    sys_, params = lm
    eng = serving.ServingEngine(sys_, params, lanes=2, max_len=12, chunk=3,
                                donate=False)
    rng = np.random.default_rng(0)
    reqs = [api.ServeRequest(
        tokens=tuple(rng.integers(1, sys_.cfg.vocab_size, 4 + i % 2)),
        max_new_tokens=(4, 5, 3, 1)[i]) for i in range(4)]
    eng.run(reqs[:2])                      # every program compiled here
    eng.reset()
    obs.take()
    with jax.profiler.trace(str(tmp_path)):
        for r in reqs:
            eng.submit(r)
        eng.step()
        live = eng.live()
        eng.run()
    spans, counters = obs.take()

    assert [(x.lane, x.request, len(x.tokens)) for x in live] == [
        (0, reqs[0], 3), (1, reqs[1], 3)]
    assert all(x.t_first is not None for x in live)
    comps = eng.completions
    assert len(comps) == 4
    for c in comps:
        assert c.t_admit <= c.t_first <= c.t_done
    assert counters["serve.tokens"] == sum(len(c.tokens) for c in comps)
    assert counters["serve.chunks"] == eng.chunks_run
    assert counters["serve.lane_slots"] == 2 * eng.rounds_run
    assert {k for k in counters if not k.startswith("compiles.")} == {
        "serve.tokens", "serve.chunks", "serve.lane_slots"}

    by_id = {s.id: s for s in spans}
    steps = [s for s in spans if s.name == "serve.step"]
    assert len(steps) == eng.chunks_run
    for s in spans:
        if s.name in ("serve.admit", "serve.read_remaining", "serve.decode",
                      "serve.sync", "serve.harvest"):
            assert by_id[s.parent_id].name == "serve.step"
    prefills = [s for s in spans if s.name == "serve.prefill"]
    assert sorted(p.ids["nonce"] for p in prefills) == [0, 1, 2, 3]
    assert {by_id[p.parent_id].name for p in prefills} == {"serve.admit"}
    reqs_seen = {s.ids["nonce"]: s for s in spans
                 if s.name == "serve.request"}
    for c in comps:
        r = reqs_seen[c.nonce]
        assert r.ids["t_first"] == c.t_first and r.ids["t_admit"] == c.t_admit
        assert r.end_ns == round(c.t_done * 1e9)


def test_trainer_chunk_spans_nest(lm, tmp_path):
    sys_, params = lm
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 2, 7), 0,
                              sys_.cfg.vocab_size)
    batches = [{"tokens": toks[i, :, :-1], "labels": toks[i, :, 1:]}
               for i in range(2)]
    trainer = api.build_trainer(sys_, api.TrainConfig(chunk=2,
                                                      donate=False))
    state = trainer.init(params)
    trainer.run(state, batches)
    obs.take()
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(trainer.run(state, batches))
    spans, counters = obs.take()
    chunk, = [s for s in spans if s.name == "train.chunk"]
    assert chunk.ids == {"steps": 2}
    kids = [s.name for s in spans if s.parent_id == chunk.id]
    assert kids == ["train.stack_batches", "train.dispatch"]
    assert not any(k.startswith("compiles.train.dispatch") for k in counters)
