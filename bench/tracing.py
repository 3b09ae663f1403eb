"""Reduction of a profiler trace to the numbers the per-layer readers use.

JAX's profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Each TPU is a plane ``/device:TPU:<n>`` whose line ``XLA Ops``
holds one event per operation run on the chip and whose line ``XLA
Modules`` holds one event per program run. The benchmark's own host spans
(``bench.<name>``, from ``Context.span``) sit on a host plane; the span
``bench.window`` bounds the measured window.

From these:

* busy time: the union of the operations' intervals inside the window,
  per chip, averaged over the chips; idle share is one minus busy over
  the window;
* device time by program: the programs' intervals by name, the ``(id)``
  suffix dropped, on the chip that spent the most on each;
* exposed collective time: the part of the collectives' intervals during
  which no other operation runs on that chip;
* idle gaps: the gaps in the busy union, each named by the host span that
  covers most of it.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all")
WINDOW_SPAN = "bench.window"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy)


def _program_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """``%fusion.3 = f32[8,128]{...} fusion(...)`` becomes
    ``fusion.3 f32[8,128]``."""
    m = re.match(r"%?([\w.\-]+) = \(?(\w+\[[^\]]*\])?", name)
    if not m:
        return name[:80]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


Event = Tuple[str, float, float]


def exclusive(events: List[Event]) -> List[Tuple[Event, float]]:
    """Each event with its own time: its duration less that of the events
    nested in it (a loop's body operations are events of their own)."""
    out: List[Tuple[Event, float]] = []
    stack: List[list] = []                  # [event, time of children]
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0][2] <= ev[1]:
            top, kids = stack.pop()
            out.append((top, top[2] - top[1] - kids))
        if stack:
            stack[-1][1] += ev[2] - ev[1]
        stack.append([ev, 0.0])
    while stack:
        top, kids = stack.pop()
        out.append((top, top[2] - top[1] - kids))
    return out


@dataclass
class DeviceTrace:
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class TraceSummary:
    window: Interval                           # seconds, trace clock
    devices: List[DeviceTrace]
    host_spans: List[Tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _busy(self, d: DeviceTrace) -> List[Interval]:
        ev = d.ops or d.modules
        return union(clip([(s, e) for _, s, e in ev], *self.window))

    @property
    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(total(self._busy(d)) for d in self.devices) \
            / len(self.devices)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_seconds(self) -> Dict[str, float]:
        """Device seconds by program name on the chip that spent the most
        on it (a program placed on one chip of four counts in full)."""
        out: Dict[str, float] = {}
        for d in self.devices:
            mine: Dict[str, float] = {}
            for n, s, e in d.modules:
                for cs, ce in clip([(s, e)], *self.window):
                    key = _program_name(n)
                    mine[key] = mine.get(key, 0.0) + (ce - cs)
            for k, v in mine.items():
                out[k] = max(out.get(k, 0.0), v)
        return out

    def op_seconds(self) -> Dict[str, float]:
        """Own device time of each operation inside the window, by
        ``<program>/<operation>``, averaged over the chips."""
        import bisect
        out: Dict[str, float] = {}
        lo, hi = self.window
        for d in self.devices:
            mods = sorted(d.modules, key=lambda m: m[1])
            starts = [m[1] for m in mods]
            inside = [(n, s, e) for n, s, e in d.ops if s >= lo and e <= hi]
            for (n, s, _), own in exclusive(inside):
                i = bisect.bisect_right(starts, s) - 1
                prog = (_program_name(mods[i][0])
                        if i >= 0 and mods[i][2] >= s else "?")
                key = prog + "/" + _op_name(n)
                out[key] = out.get(key, 0.0) + own
        k = max(1, len(self.devices))
        return {name: v / k for name, v in out.items()}

    def collective_seconds(self) -> Tuple[float, float]:
        """(collective time, the part of it no other operation overlaps),
        averaged over the chips."""
        tot = exp = 0.0
        for d in self.devices:
            coll = union(clip([(s, e) for n, s, e in d.ops
                               if COLLECTIVE.search(n)], *self.window))
            other = union(clip([(s, e) for n, s, e in d.ops
                                if not COLLECTIVE.search(n)], *self.window))
            tot += total(coll)
            exp += total(subtract(coll, other))
        n = max(1, len(self.devices))
        return tot / n, exp / n

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Every gap of the first chip's busy union, longest first, named
        by the host span (other than the window) that covers most of it."""
        if not self.devices:
            return []
        spans = [(n, s, e) for n, s, e in self.host_spans
                 if n != WINDOW_SPAN]
        out = []
        for gs, ge in gaps(self._busy(self.devices[0]), *self.window):
            best, cover = "no host span", 0.0
            for n, s, e in spans:
                c = min(e, ge) - max(s, gs)
                if c > cover:
                    best, cover = n, c
            out.append((best, ge - gs))
        return sorted(out, key=lambda g: -g[1])

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps()[:n]]}


def reduce(path: str, *, n_devices: int) -> TraceSummary:
    """Read an ``.xplane.pb`` into a ``TraceSummary`` (times in seconds)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, DeviceTrace] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if DEVICE_PLANE.fullmatch(plane.name):
            idx = int(plane.name.rsplit(":", 1)[1])
            d = devices.setdefault(idx, DeviceTrace())
            for line in plane.lines:
                if line.name == "XLA Ops":
                    d.ops.extend((ev.name, ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9)
                                 for ev in line.events)
                elif line.name == "XLA Modules":
                    d.modules.extend((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9)
                                     for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9)
                            for ev in line.events
                            if ev.name.startswith("bench."))
    win = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    used = [devices[i] for i in sorted(devices)][:n_devices]
    return TraceSummary(window=win[0], devices=used, host_spans=host)
