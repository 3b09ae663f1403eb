"""The program's own spans and counters (``repro.obs``), for the readers
of a traced run.

The program keeps its records while a profiler trace runs, which in a
traced run is the measured window and nothing else. ``records(run)``
takes them from the program once per run and hands the same records to
every reader. A program that keeps none (no ``repro.obs``) gives None,
and the readers then read nothing.

The program times its spans on the host's ``perf_counter``; the trace
has a clock of its own. ``trace_offset_s`` pairs the k-th span the program
names ``program`` with the k-th runner span named ``runner`` (each runner
span wraps one such program call) and takes the median of their starts'
differences as the offset. Where the counts differ, or the differences'
interquartile range passes ``MAX_SPREAD_S``, it gives None: the spans are
then not placed on the trace, and a metric that needs them is left out.
"""
from __future__ import annotations

import statistics
from typing import List, Optional

MAX_SPREAD_S = 50e-6

_last = (None, None)        # (the run, its records)


def records(run):
    """``(spans, counters)`` the program kept in this run, or None."""
    global _last
    if _last[0] is not run:
        try:
            from repro import obs
        except ImportError:
            got = None
        else:
            got = obs.take()
        _last = (run, got)
    return _last[1]


def named(spans, name: str) -> list:
    return [s for s in spans if s.name == name]


def mean_ms(spans) -> Optional[float]:
    """Mean duration of perf_counter-timed spans, in ms (None if none)."""
    if not spans:
        return None
    return 1e-6 * sum(s.end_ns - s.start_ns for s in spans) / len(spans)


def trace_offset_s(run, spans, program: str, runner: str = "bench.step"
                   ) -> Optional[float]:
    """Seconds to add to a program span's ``perf_counter`` time to put it
    on the trace's clock, or None where the pairing does not hold."""
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    theirs = sorted(s for n, s, e in run.trace.host_spans
                    if n == runner and lo <= s and e <= hi)
    mine = sorted(s.start_ns for s in spans if s.name == program)
    if not mine or len(mine) != len(theirs):
        return None
    offsets: List[float] = [t - m * 1e-9 for m, t in zip(mine, theirs)]
    if len(offsets) > 1:
        q1, _, q3 = statistics.quantiles(offsets, n=4, method="inclusive")
        if q3 - q1 > MAX_SPREAD_S:
            return None
    return statistics.median(offsets)
