"""From a profiler trace and the mux's spans to per-layer numbers.

A traced run writes one ``jax.profiler`` trace of its window (an
``.xplane.pb`` file) and keeps the mux's ``repro.obs`` spans.  This module
reduces both:

- the window is the host annotation ``bench.window``;
- device busy time is the union, within the window, of the intervals of
  the device's operations (the ``XLA Ops`` line of each chip's plane,
  ``/device:TPU:<k>``), averaged over the chips used; the idle share is
  one less busy over the window;
- each idle gap on the device is split among the host activities that
  overlap it: the mux's phase spans (``mux.plan``, ``mux.coalesce``, ...),
  put on the trace's clock through the ``bench.tick`` annotations, and the
  harness's ``bench.feed``; what none covers is ``other``;
- the device time of one program is the total of its module events (the
  ``XLA Modules`` line), by a part of the module's name.

``Context`` is what a per-layer metric's reader (``bench/metrics/``)
receives.
"""

from __future__ import annotations

import bisect
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

WINDOW = "bench.window"
TICK = "bench.tick"
FEED = "bench.feed"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def newest_trace(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class Reduced(NamedTuple):
    busy_s: float  # device busy seconds in the window, mean over chips
    window_s: float  # the traced window's length
    ops: Dict[str, float]  # device seconds by operation name (all chips)
    modules: Dict[str, float]  # device seconds by program (module) name
    idle: Dict[str, float]  # idle device seconds by host activity
    gaps: int  # idle gaps in the window

    def breakdown(self, top: int = 10) -> dict:
        def best(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:top]]
        return {"device_ops": best(self.ops), "idle_gaps": best(self.idle)}

    def module_s(self, part: str) -> float:
        return sum(v for k, v in self.modules.items() if part in k)


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns)


def _op_name(name: str, start: float, progs, prog_starts) -> str:
    """``<program>/<instruction>`` of an XLA op event, whose name is the
    whole HLO instruction."""
    short = name.split(" = ")[0].strip()
    i = bisect.bisect_right(prog_starts, start) - 1
    if i >= 0 and progs[i][0] <= start < progs[i][1]:
        return f"{progs[i][2]}/{short}"
    return short


def reduce(trace_dir: Path, spans, logs, t0: float, chips: int = 1,
           data=None) -> Reduced:
    """Reduce the newest trace under ``trace_dir``.

    ``spans`` are the mux's ``SpanRecord``s (perf_counter seconds),
    ``logs`` the harness's tick logs (``start_s`` from ``t0``); ``data``
    is an already loaded ``ProfileData`` (tests)."""
    if data is None:
        from jax.profiler import ProfileData
        data = ProfileData.from_file(str(newest_trace(trace_dir)))
    window = None
    ticks: List[float] = []
    feeds: List[Tuple[float, float]] = []
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            # A chip's plane has XLA's lines; others (a custom tracer's,
            # with none) are not chips.
            if any(ln.name in (OPS_LINE, MODULES_LINE) for ln in plane.lines):
                devices.append(plane)
            continue
        for line in plane.lines:
            for name, s, d in _events(line):
                if name == WINDOW and window is None:
                    window = (s, s + d)
                elif name == TICK:
                    ticks.append(s)
                elif name == FEED:
                    feeds.append((s, s + d))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    if not devices:
        raise ValueError("the trace holds no device plane")
    w0, w1 = window
    ops: Dict[str, float] = {}
    modules: Dict[str, float] = {}
    busy_each = []
    busy_union: List[Tuple[float, float]] = []
    for plane in sorted(devices, key=lambda p: p.name)[:chips]:
        lines = list(plane.lines)
        progs = []  # (start, end, program) of each module run
        for ln in lines:
            if ln.name == MODULES_LINE:
                for name, s, d in _events(ln):
                    progs.append((s, s + d, name.split("(")[0]))
                    a, b = max(s, w0), min(s + d, w1)
                    if b > a:
                        modules[name] = modules.get(name, 0.0) + \
                            (b - a) * 1e-9
        progs.sort()
        prog_starts = [p[0] for p in progs]
        op_lines = [ln for ln in lines if ln.name == OPS_LINE] or \
            [ln for ln in lines if ln.name == MODULES_LINE]
        iv = []
        for ln in op_lines:
            for name, s, d in _events(ln):
                a, b = max(s, w0), min(s + d, w1)
                if b > a:
                    iv.append((a, b))
                    key = _op_name(name, s, progs, prog_starts)
                    ops[key] = ops.get(key, 0.0) + (b - a) * 1e-9
        u = _union(iv)
        busy_each.append(sum(b - a for a, b in u))
        busy_union.extend(u)
    busy = _union(busy_union)
    gaps = []
    at = w0
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if w1 > at:
        gaps.append((at, w1))

    # Host activity on the trace's clock.
    host: List[Tuple[float, float, str]] = [(a, b, FEED) for a, b in feeds]
    if ticks and logs:
        starts = np.sort(np.asarray(ticks))
        mine = np.asarray([(t0 + lg.start_s) * 1e9 for lg in logs])
        k = min(starts.size, mine.size)
        offset = float(np.median(starts[:k] - mine[:k]))
        for r in spans:
            if r.name.startswith("mux.") and r.name != "mux.tick":
                a = r.ts * 1e9 + offset
                host.append((a, a + r.dur * 1e9, r.name))
    host.sort()
    host_starts = [h[0] for h in host]
    idle: Dict[str, float] = {}
    for a, b in gaps:
        left = b - a
        i = max(0, bisect.bisect_right(host_starts, a) - 1)
        while i < len(host) and host[i][0] < b:
            ha, hb, name = host[i]
            ov = min(b, hb) - max(a, ha)
            if ov > 0:
                idle[name] = idle.get(name, 0.0) + ov * 1e-9
                left -= ov
            i += 1
        if left > 0:
            idle["other"] = idle.get("other", 0.0) + left * 1e-9
    return Reduced(float(np.mean(busy_each)) * 1e-9, (w1 - w0) * 1e-9, ops,
                   modules, idle, len(gaps))


class Context:
    """What a per-layer metric's ``read(ctx)`` gets.

    ``ticks`` is the number of timed ticks; ``phase_ms(name)`` the self
    time per tick of the mux's ``name`` spans in the window (a span's
    duration less that of the spans directly inside it); ``vetted_lengths``
    the window length of every window the timed ticks vetted; ``peaks`` the
    device's row of ``bench/peaks.json``; ``gc_ms()`` the collector's time
    per tick in the window, from ``gc_passes`` (``(generation, start,
    seconds)``, as ``harness.GcLog`` records them); ``host_value(name)`` a
    number the harness took on the host's clock over the whole window, from
    ``host`` (``{name: value}``), such as a latency quantile."""

    def __init__(self, reduced: Reduced, spans, logs, vetted0, fleet,
                 peaks: Optional[dict], t0: float, t_end: float,
                 gc_passes=None, host: Optional[dict] = None):
        self.trace = reduced
        self.host = dict(host or {})
        self.gc_s = None if gc_passes is None else sum(
            d for _, s, d in gc_passes if s >= t0 and s + d <= t_end)
        self.ticks = len(logs)
        self.peaks = peaks
        inside = [r for r in spans if r.ts >= t0 and r.ts + r.dur <= t_end]
        child: Dict[int, float] = {}
        for r in inside:
            if r.parent is not None:
                child[r.parent] = child.get(r.parent, 0.0) + r.dur
        self._self: Dict[str, float] = {}
        for r in inside:
            self._self[r.name] = (self._self.get(r.name, 0.0) + r.dur
                                  - child.get(r.sid, 0.0))
        served = logs[-1].vetted - vetted0
        self.vetted_lengths = np.repeat(fleet.windows, served)

    def phase_ms(self, *names: str) -> Optional[float]:
        if not any(n in self._self for n in names):
            return None
        return 1e3 * sum(self._self.get(n, 0.0) for n in names) / self.ticks

    def gc_ms(self) -> Optional[float]:
        if self.gc_s is None:
            return None
        return 1e3 * self.gc_s / self.ticks

    def host_value(self, name: str) -> Optional[float]:
        return self.host.get(name)

    def idle_share(self) -> Optional[float]:
        if self.trace.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.trace.busy_s / self.trace.window_s)
