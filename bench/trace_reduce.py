"""Reduce JAX profiler traces of the window to the numbers the benchmark
reports: the device's busy and idle time, device time and loop iterations
per XLA program, the top device operations, and the longest idle gaps
labelled by what the host was doing.

A run traces the window in short bursts (`run.TraceSampler`), one profiler
session each. Each burst is flattened to `Event`s (plane, line, name, start,
end in ns from the session's start) in a `Trace`, so the arithmetic below
runs the same on a hand-built or recorded slice (`bench/tests/`) as on a
live `.xplane.pb`; a `Sample` adds up its bursts.

Device planes are those named `/device:TPU:<n>`. On them:
  * the "XLA Ops" line holds one event per operation run. A control-flow
    op (`%while`, `%conditional`) is an event enclosing the ops of its
    body, so only leaf ops count as work: busy time is the union of their
    intervals, and the gaps between back-to-back ops count as idle;
  * the "XLA Modules" line holds one event per program run, named after the
    jitted function (`jit__sim_batch_stacked(...)`), clipped to the session;
    an op belongs to the module event that encloses its start.
A burst's traced span runs from its first to its last event on any plane.
Host spans are the benchmark's own `jax.profiler.TraceAnnotation`s, whose
names start with `bench.`.
"""
from __future__ import annotations

import bisect
import glob
import os
import statistics
import sys
from collections import defaultdict
from functools import cached_property
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
SWEEP_LABEL = "bench.sweep "


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def xplane_paths(trace_dir: str) -> List[str]:
    """Every session's trace under `trace_dir`, in the order written."""
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def load_xplane(path: str, label: str = "host") -> "Trace":
    """One session's device op and module events and the benchmark's host
    spans; its span covers every event of every plane."""
    from jax.profiler import ProfileData

    out = []
    lo, hi = float("inf"), float("-inf")
    for plane in ProfileData.from_file(path).planes:
        dev = is_device_plane(plane.name)
        for line in plane.lines:
            keep = dev and line.name in (OPS_LINE, MODULES_LINE)
            for e in line.events:
                s, t = float(e.start_ns), float(e.end_ns)
                lo, hi = min(lo, s), max(hi, t)
                name = e.name
                if keep or (not dev and name.startswith(HOST_PREFIX)):
                    out.append(Event(plane.name, line.name, sys.intern(name),
                                     s, t))
    return Trace(out, span=(lo, hi) if lo < hi else None, label=label)


def _merge(intervals) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _covered(intervals) -> float:
    return sum(e - s for s, e in _merge(intervals))


def _enclosing(inner: Sequence[Event], outer: Sequence[Event]
               ) -> Dict[int, List[Event]]:
    """{index into `outer`: events of `inner` that start inside it}; the
    `outer` intervals are sorted and do not overlap one another."""
    starts = [o.start_ns for o in outer]
    out: Dict[int, List[Event]] = defaultdict(list)
    for e in inner:
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i >= 0 and e.start_ns < outer[i].end_ns:
            out[i].append(e)
    return out


def module_family(name: str) -> str:
    """`jit__sim_batch_stacked(...)` -> `_sim_batch_stacked`."""
    base = name.split("(")[0]
    return base[4:] if base.startswith("jit_") else base


def op_label(name: str) -> str:
    """`%fusion.12 = f32[128,9]{...} fusion(...)` -> `%fusion.12`."""
    return name.split(" = ")[0]


def loop_iterations(names: Sequence[str]) -> float:
    """Loop iterations in one program run's ops, in the order they ran.
    Every op of a loop body runs once per iteration, so the distance
    between two successive runs of one op is the number of ops an
    iteration runs; the iterations are the runs of the ops that ran more
    than once over the median such distance (0 when no op ran twice). An op
    outside the loops, or under a `cond` that fired once, ran once and does
    not count; a burst that cuts an iteration counts the part it holds."""
    last: Dict[str, int] = {}
    runs: Dict[str, int] = defaultdict(int)
    dists = []
    for i, name in enumerate(names):
        if name in last:
            dists.append(i - last[name])
        last[name] = i
        runs[name] += 1
    if not dists:
        return 0.0
    return sum(n for n in runs.values() if n > 1) / statistics.median(dists)


class Trace:
    """One burst, flattened, with the per-device views the metrics share
    worked out once. `span` is the session's (first, last) event time;
    `label` names what the host was doing when the burst began."""

    def __init__(self, events: Sequence[Event],
                 span: Optional[Tuple[float, float]] = None,
                 label: str = "host"):
        self.events = list(events)
        self.span = span
        self.label = label
        self.planes = sorted({e.plane for e in self.events
                              if is_device_plane(e.plane)})
        by_line: Dict[Tuple[str, str], List[Event]] = defaultdict(list)
        for e in self.events:
            by_line[e.plane, e.line].append(e)
        for evs in by_line.values():
            evs.sort(key=lambda e: (e.start_ns, -e.end_ns))
        self._by_line = by_line
        self._ops: Dict[str, List[Event]] = {}

    def ops(self, plane: str) -> List[Event]:
        """The plane's leaf ops, in time order: an op that encloses the
        next op is a control-flow op and is left out."""
        if plane not in self._ops:
            evs = self._by_line.get((plane, OPS_LINE), [])
            self._ops[plane] = [
                e for e, nxt in zip(evs, evs[1:] + [None])
                if nxt is None or not (nxt.start_ns < e.end_ns
                                       and nxt.end_ns <= e.end_ns)]
        return self._ops[plane]

    def modules(self, plane: str) -> List[Event]:
        return self._by_line.get((plane, MODULES_LINE), [])

    def host_spans(self) -> List[Event]:
        return [e for e in self.events if e.name.startswith(HOST_PREFIX)]

    def window(self) -> Tuple[float, float]:
        """The traced span: the session's, or else first to last event."""
        if self.span is not None:
            return self.span
        if not self.events:
            return 0.0, 0.0
        return (min(e.start_ns for e in self.events),
                max(e.end_ns for e in self.events))

    def window_s(self) -> float:
        w0, w1 = self.window()
        return (w1 - w0) / 1e9

    def busy_s(self) -> float:
        """Seconds in which a leaf op ran, averaged over the devices."""
        if not self.planes:
            return 0.0
        return sum(_covered((o.start_ns, o.end_ns) for o in self.ops(p))
                   for p in self.planes) / len(self.planes) / 1e9

    def family_stats(self) -> Dict[str, Dict[str, float]]:
        """Per module family: `op_ns`, the union of its leaf ops'
        intervals, and `iterations`, the loop iterations its runs made in
        the burst (`loop_iterations` of each module event's ops)."""
        out: Dict[str, Dict[str, float]] = defaultdict(
            lambda: {"op_ns": 0.0, "iterations": 0.0})
        for plane in self.planes:
            mods = self.modules(plane)
            for i, evs in _enclosing(self.ops(plane), mods).items():
                fam = out[module_family(mods[i].name)]
                end = mods[i].end_ns
                fam["op_ns"] += _covered((o.start_ns, min(o.end_ns, end))
                                         for o in evs)
                fam["iterations"] += loop_iterations([o.name for o in evs])
        return dict(out)

    def op_time(self) -> Dict[str, float]:
        """{"family:op": ns} of the leaf ops, summed over devices."""
        tot: Dict[str, float] = defaultdict(float)
        for plane in self.planes:
            mods = self.modules(plane)
            fam = {id(o): module_family(mods[i].name)
                   for i, evs in _enclosing(self.ops(plane), mods).items()
                   for o in evs}
            for o in self.ops(plane):
                tot[f"{fam.get(id(o), '?')}:{op_label(o.name)}"] += o.dur_ns
        return tot

    def gaps(self) -> List[Tuple[str, float]]:
        """Every stretch of the traced span in which no op ran on the first
        device, as (label, ns): the innermost benchmark span that covers
        its midpoint, or else the burst's label."""
        if not self.planes:
            w0, w1 = self.window()
            return [(self.label, w1 - w0)] if w1 > w0 else []
        busy = _merge((o.start_ns, o.end_ns) for o in self.ops(self.planes[0]))
        w0, w1 = self.window()
        gaps, t = [], w0
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < w1:
            gaps.append((t, w1))
        host = self.host_spans()
        out = []
        for s, e in gaps:
            mid = (s + e) / 2
            cover = [h for h in host if h.start_ns <= mid <= h.end_ns]
            label = min(cover, key=lambda h: h.dur_ns).name if cover \
                else self.label
            out.append((label, e - s))
        return out


class Sample:
    """The bursts of one run, added up: busy and traced seconds, device
    time and iterations per module family, op time and idle gaps."""

    def __init__(self, traces: Sequence[Trace]):
        self.traces = list(traces)

    def in_sweeps(self) -> "Sample":
        """The bursts that began inside a window sweep (label `bench.sweep
        <k>`): the device's own idle time, without the host's work between
        sweeps."""
        return Sample([t for t in self.traces
                       if t.label.startswith(SWEEP_LABEL)])

    def busy_s(self) -> float:
        return sum(t.busy_s() for t in self.traces)

    def window_s(self) -> float:
        return sum(t.window_s() for t in self.traces)

    def idle_share(self) -> Optional[float]:
        """1 - busy / traced seconds; None without a device op."""
        if not any(t.ops(p) for t in self.traces for p in t.planes):
            return None
        return 1.0 - self.busy_s() / self.window_s()

    @cached_property
    def _family_stats(self) -> List[Dict[str, Dict[str, float]]]:
        return [t.family_stats() for t in self.traces]

    def family_stats(self) -> Dict[str, Dict[str, float]]:
        out: Dict[str, Dict[str, float]] = defaultdict(
            lambda: {"op_ns": 0.0, "iterations": 0.0})
        for stats in self._family_stats:
            for fam, st in stats.items():
                for k, v in st.items():
                    out[fam][k] += v
        return dict(out)

    def bursts_holding(self) -> Dict[str, int]:
        """{module family: bursts in which a run of it ran a leaf op}."""
        out: Dict[str, int] = defaultdict(int)
        for stats in self._family_stats:
            for fam in stats:
                out[fam] += 1
        return dict(out)

    def top_ops(self, n: int = 10) -> List[List]:
        """[["family:op", seconds], ...] of the leaf ops with the most
        device time, averaged over devices."""
        tot: Dict[str, float] = defaultdict(float)
        for t in self.traces:
            k = max(len(t.planes), 1)
            for name, ns in t.op_time().items():
                tot[name] += ns / k
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in best]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The `n` longest idle stretches of any burst, labelled:
        [[label, seconds], ...]."""
        gaps = [g for t in self.traces for g in t.gaps()]
        return [[label, ns / 1e9]
                for label, ns in sorted(gaps, key=lambda g: -g[1])[:n]]
