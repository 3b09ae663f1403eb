"""The benchmark's core: run one cell and print its result line.

Nothing here knows a cell, a model or a metric by name. ``BENCHMARK.json``
names the cell; the cell names its configuration and traffic mix, each a
data file under ``bench/``; the traffic mix names its runner; each
per-layer metric is a reader module of its own. A later change adds a cell
or a metric by adding such files and an entry in ``BENCHMARK.json``.

A runner module (``bench/runners/<name>.py``) has one function,
``run(ctx) -> Outcome``: it builds the system under test from the
configuration, warms every program the traffic uses, runs the measured
window inside ``ctx.window()``, and returns the end-to-end numbers it
measured with a ``verify`` callable. The harness calls ``verify`` after it
has read the device's peak memory; ``verify`` frees the program's state,
runs the cell's plain reference and returns the numbers compared, each
with its limit from ``bench/limits/<cell>.json``.

A per-layer reader (``bench/metrics/<metric>.py``) has one function,
``read(run) -> float | None`` over a ``RunView``; it returns None where
the run holds nothing for it to read, and the metric is then left out.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = "bench"
CACHE_DIR = ".bench_cache"          # inside the checkout, fixed path


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, a missing file)."""


def load_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise BenchError(f"missing file {path}") from e


def load_module(path: str, name: str):
    """Import a module from a file path (names may hold dots)."""
    if not os.path.isfile(path):
        raise BenchError(f"missing module {path}")
    mod_name = "bench_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Spec:
    """``BENCHMARK.json``, with the lookups the harness needs."""
    raw: dict

    @classmethod
    def load(cls, root: str) -> "Spec":
        return cls(load_json(os.path.join(root, "BENCHMARK.json")))

    def cell(self, name: str) -> dict:
        for w in self.raw["workloads"]:
            if w["name"] == name:
                return w
        raise BenchError(f"no workload named {name!r} in BENCHMARK.json")

    def end_to_end_for(self, cell: str) -> List[dict]:
        return [m for m in self.raw["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer_for(self, cell: str) -> List[dict]:
        e2e = {m["name"] for m in self.end_to_end_for(cell)}
        return [m for m in self.raw["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


@dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Outcome:
    """What a runner returns: the window's work, its numbers, its check."""
    attempted: int
    failed: int
    metrics: Dict[str, float]
    counters: Dict[str, float]
    verify: Callable[[], List[Check]]


@dataclass
class RunView:
    """What a per-layer reader sees of a traced run."""
    peaks: dict
    kind: str                       # the device kind, a key of peaks
    chips: int
    window_s: float
    counters: Dict[str, float]
    trace: Any                      # tracing.TraceSummary


class _CompileCounter:
    """Counts compile requests (cache hits included) while armed."""

    def __init__(self):
        self.armed = False
        self.count = 0

    def __call__(self, event: str, **_):
        if self.armed and event.endswith("compile_requests_use_cache"):
            self.count += 1

    def on_duration(self, event: str, _secs: float, **_):
        if self.armed and event.endswith("backend_compile_duration"):
            self.count += 1


class Context:
    """Everything a runner gets: the cell's files, the seed, the clock."""

    def __init__(self, root: str, spec: Spec, cell: dict, seed: int,
                 seconds: float, trace: bool, t0: float):
        bench = os.path.join(root, BENCH_DIR)
        self.root = root
        self.spec = spec
        self.cell = cell
        self.config = load_json(
            os.path.join(bench, "configs", cell["config"] + ".json"))
        self.traffic = load_json(
            os.path.join(bench, "traffic", cell["traffic"] + ".json"))
        self.limits = load_json(
            os.path.join(bench, "limits", cell["name"] + ".json"))
        self.runner_path = os.path.join(
            bench, "runners", self.traffic["runner"] + ".py")
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.chips = int(cell["chips"])
        self.t0 = t0
        self.peaks = load_json(os.path.join(bench, "peaks.json"))
        self.cache_dir = os.path.join(root, CACHE_DIR)
        self.trace_dir = os.path.join(self.cache_dir, "trace", cell["name"])
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.devices: List[Any] = []
        self.compiles = _CompileCounter()

    # -- the seed ------------------------------------------------------------
    def key(self, salt: int = 0):
        """A JAX key from the seed; seeds past 32 bits fold in their top."""
        import jax
        s = self.seed + salt
        return jax.random.fold_in(jax.random.PRNGKey(s & 0x7FFFFFFF), s >> 31)

    def rng(self, salt: int = 0):
        import numpy as np
        return np.random.default_rng([self.seed, salt])

    # -- the window ----------------------------------------------------------
    @property
    def window_seconds(self) -> float:
        """Length of the measured loop: a traced run traces a short one."""
        if self.trace:
            return min(self.seconds, float(self.traffic.get("trace_seconds",
                                                            3)))
        return self.seconds

    def span(self, name: str):
        """A host span in the profiler's trace (no cost when not tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    @contextlib.contextmanager
    def window(self):
        """The measured window. Set-up ends where it starts; the runner
        blocks on its last result before leaving it."""
        import jax
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.compiles.armed = True
        t = time.perf_counter()
        self.setup_s = t - self.t0
        with self.span("window"):
            yield
        self.window_s = time.perf_counter() - t
        self.compiles.armed = False
        if self.trace:
            jax.profiler.stop_trace()

    def log(self, msg: str):
        """A progress line on standard error, stamped from process start."""
        print(f"[{time.perf_counter() - self.t0:8.2f} s] {msg}",
              file=sys.stderr, flush=True)

    def memory_peak_bytes(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks))


def _parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _init_jax(ctx: Context, require_chip: bool):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ctx.cache_dir,
                                                           "jax")
    import jax
    import jax.monitoring
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    # every program goes into the cache, however quick its compile, so
    # that only a cell's first run in a checkout compiles: at JAX's
    # default threshold (1 s) the small programs the classifier's eager
    # mask synthesis compiles anew each round are served, or not, by
    # whatever cache the TPU compiler keeps on the machine, and a round
    # took 422 ms or 125 ms by that cache's state (on a TPU v5 lite)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.monitoring.register_event_listener(ctx.compiles)
    jax.monitoring.register_event_duration_secs_listener(
        ctx.compiles.on_duration)
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise BenchError(
                f"no TPU: JAX found platform {devs[0].platform!r} "
                f"({devs[0].device_kind}); the benchmark measures only a TPU")
        if devs[0].device_kind not in ctx.peaks["devices"]:
            raise BenchError(f"device kind {devs[0].device_kind!r} is not in "
                             f"bench/peaks.json")
    if len(devs) < ctx.chips:
        raise BenchError(f"the cell asks for {ctx.chips} chips; JAX sees "
                         f"{len(devs)}")
    ctx.devices = devs[:ctx.chips]


def _readers(ctx: Context) -> List[tuple]:
    out = []
    for m in ctx.spec.per_layer_for(ctx.cell["name"]):
        path = os.path.join(ctx.root, BENCH_DIR, "metrics", m["name"] + ".py")
        out.append((m, load_module(path, m["name"])))
    return out


def open_context(root: str, workload: str, seed: int, seconds: float,
                 trace: bool, t0: float, require_chip: bool = True
                 ) -> Context:
    """A cell's context with JAX initialized on its chips."""
    spec = Spec.load(root)
    ctx = Context(root, spec, spec.cell(workload), seed, seconds, trace, t0)
    _init_jax(ctx, require_chip)
    return ctx


def load_runner(ctx: Context):
    return load_module(ctx.runner_path, ctx.traffic["runner"])


def run_cell(argv, *, root: str, t0: float, require_chip: bool = True
             ) -> dict:
    """One run of one cell; returns the result line as a dict."""
    args = _parse(argv)
    ctx = open_context(root, args.workload, args.seed, args.seconds,
                       bool(args.trace), t0, require_chip)
    spec = ctx.spec
    readers = _readers(ctx) if ctx.trace else []
    out: Outcome = load_runner(ctx).run(ctx)
    if ctx.window_s is None:
        raise BenchError("the runner ran no measured window")
    memory_peak = ctx.memory_peak_bytes()

    metrics: Dict[str, dict] = {}
    device = {"platform": ctx.devices[0].platform,
              "kind": ctx.devices[0].device_kind, "count": ctx.chips,
              "memory_peak_bytes": memory_peak}
    breakdown = None
    if ctx.trace:
        from bench import tracing
        summary = tracing.reduce(tracing.find_xplane(ctx.trace_dir),
                                 n_devices=ctx.chips)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        view = RunView(peaks=ctx.peaks, kind=ctx.devices[0].device_kind,
                       chips=ctx.chips,
                       window_s=ctx.window_s, counters=out.counters,
                       trace=summary)
        for m, reader in readers:
            v = reader.read(view)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
    else:
        for m in spec.end_to_end_for(ctx.cell["name"]):
            if m["name"] == "setup_s":
                v = ctx.setup_s
            elif m["name"] in out.metrics:
                v = out.metrics[m["name"]]
            else:
                raise BenchError(f"runner reported no {m['name']}")
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    checks = out.verify()
    result = {"correct": bool(checks) and all(c.ok for c in checks),
              "attempted": int(out.attempted), "failed": int(out.failed),
              "metrics": metrics, "device": device,
              "compiles_in_window": ctx.compiles.count,
              "window_s": ctx.window_s}
    if breakdown is not None:
        result["breakdown"] = breakdown
    for name, m in metrics.items():
        if not math.isfinite(m["value"]):
            raise BenchError(f"{name} is {m['value']}")
    # a check's number that is not finite fails it; JSON gets its name
    result["checks"] = {c.name: {"value": c.value if math.isfinite(c.value)
                                 else str(c.value), "limit": c.limit}
                        for c in checks}
    return result


def main(argv, *, root: str, t0: float, require_chip: bool = True) -> int:
    try:
        result = run_cell(argv, root=root, t0=t0, require_chip=require_chip)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(f"compiles in the window: {result['compiles_in_window']}",
          file=sys.stderr)
    for name, c in result["checks"].items():
        v = float(c["value"])
        verdict = "ok" if Check(name, v, c["limit"]).ok else "FAIL"
        print(f"check {name}: {v:.6g} (limit {c['limit']:.6g}) {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0
