"""The window's trace bursts read per stage of the cycle step: device time
per named scope, loop iterations from the loop's own condition, and idle
gaps labelled by what ran around them.

A TPU trace names each device op by its HLO instruction (`%fusion.1294 =
...`) and carries no `op_name` (on a v5e the "XLA Ops" events hold only
`device_offset_ps`, `device_duration_ps` and `Time Scale Multiplier`). The
stacked family program's optimized HLO does: each instruction's metadata
holds the path of `jax.named_scope`s it was traced under, e.g.
`jit(_sim_batch_stacked)/vmap()/while/body/closed_call/step.select/
select.eligibility/vmap(vmap())/gather`. `stacked_op_names` lowers and
compiles that program again at the cell's shapes (the persistent
compilation cache, warm from set-up, hands back the executable the run ran)
and maps instruction names to op_names. The step builders put every op of
a cycle under one of the `step.*` scopes (`repro.core.policy.STEP_SCOPES`);
an op is charged to the innermost `step.*` scope of its path, or to
`UNSCOPED` (loop control, the epilogue, and every op of a program built
without the scopes or of a family without a map).

Host spans are the benchmark's `bench.*` annotations and the program's own
`sweep` / `sweep.*` spans (`benchmarks.common.trace_span`), all on the
profiler's clock. An idle stretch inside a program run (a module event) is
labelled `<family>:in_program`; one outside any is labelled with the
innermost host span that covers its midpoint, or else with the burst's
label.

The per-layer readers in `bench/metrics/` call `from_ctx`. `bench/run.py`
hands them the run's `trace_reduce.Sample` and deletes the bursts once they
have read it; `burst_dir` finds the bursts where `run.TraceSampler` wrote
them.
"""
from __future__ import annotations

import importlib
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce as tr

HOST_PREFIXES = ("bench.", "sweep")
STEP_PREFIX = "step."
UNSCOPED = "unscoped"
STACKED = "_sim_batch_stacked"
SOLO = "_sim_batch"
# an HLO instruction and the op_name of its metadata
_HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT\s+)?(%[^\s=]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.MULTILINE)

OpNames = Dict[str, Dict[str, str]]    # family -> {instruction: op_name}


def is_host_span(name: str) -> bool:
    return name.startswith(HOST_PREFIXES)


def step_scope(op_name: str) -> str:
    """The innermost `step.*` scope of an op_name path, else `UNSCOPED`."""
    for part in reversed(op_name.split("/")):
        if part.startswith(STEP_PREFIX):
            return part
    return UNSCOPED


def is_loop_cond(op_name: str) -> bool:
    """An op of a while loop's condition computation."""
    return "/while/cond" in op_name


def load_xplane(path: str, label: str = "host") -> tr.Trace:
    """One burst as `trace_reduce.load_xplane` reads it, with the program's
    `sweep` spans among the host spans."""
    from jax.profiler import ProfileData

    out = []
    lo, hi = float("inf"), float("-inf")
    for plane in ProfileData.from_file(path).planes:
        dev = tr.is_device_plane(plane.name)
        for line in plane.lines:
            keep = dev and line.name in (tr.OPS_LINE, tr.MODULES_LINE)
            for e in line.events:
                s, t = float(e.start_ns), float(e.end_ns)
                lo, hi = min(lo, s), max(hi, t)
                if keep or (not dev and is_host_span(e.name)):
                    out.append(tr.Event(plane.name, line.name,
                                        sys.intern(e.name), s, t))
    return tr.Trace(out, span=(lo, hi) if lo < hi else None, label=label)


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} of an optimized HLO module's text."""
    return {m.group(1): m.group(2) for m in _HLO_OP_NAME.finditer(hlo_text)}


def stacked_op_names(cell) -> Dict[str, str]:
    """The instruction op_names of the cell's stacked family program, lowered
    and compiled again as `run_sweep` dispatches it: the alone rows, then
    the mixes of one sweep, drawn by the benchmark's own sampler and handed
    to the program as the window hands them (`run.program_mixes`, so a
    configuration with no GPU has no GPU draw), under the program's default
    driver. The pools are traced arguments, so the program depends on their
    shapes alone, not on the draws."""
    import numpy as np

    import cells
    from repro.core import params
    from repro.core import simulator as sim
    from repro.core import workloads as wl

    f = dict(cell.sim_fields)
    cfg = params.SimConfig(timing=params.Timing(**f.pop("timing")), **f)
    run = harness() or importlib.import_module("run")
    mixes = run.program_mixes(cells.population(cell, 0, 0))
    pool, active = wl.pool_batch(cfg, mixes)
    apool, aactive, _ = wl.alone_batch(cfg)
    pool = {k: np.concatenate([apool[k], pool[k]]) for k in pool}
    active = np.concatenate([aactive, active])
    lowered = sim._sim_batch_stacked.lower(
        cfg, sim.stackable_names(cfg, cell.policies), cell.n_cycles,
        cell.warmup, sim.DEFAULT_UNROLL, sim.DEFAULT_SKIP,
        sim.prepare_pool(pool, active.shape), active)
    return hlo_op_names(lowered.compile().as_text())


def _module_ops(t: tr.Trace, plane: str):
    """(module event, its leaf ops) of each program run on the plane."""
    mods = t.modules(plane)
    return [(mods[i], evs)
            for i, evs in sorted(tr._enclosing(t.ops(plane), mods).items())]


def _op_name(names: OpNames, family: str, op: tr.Event) -> str:
    return names.get(family, {}).get(tr.op_label(op.name), "")


def scope_time(t: tr.Trace, names: OpNames) -> Dict[str, Dict[str, float]]:
    """{family: {step scope or UNSCOPED: ns}} of the leaf ops, each clipped
    to its program run, averaged over the devices."""
    out: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    k = max(len(t.planes), 1)
    for plane in t.planes:
        for mod, evs in _module_ops(t, plane):
            name = tr.module_family(mod.name)
            for o in evs:
                out[name][step_scope(_op_name(names, name, o))] += \
                    (min(o.end_ns, mod.end_ns) - o.start_ns) / k
    return {f: dict(v) for f, v in out.items()}


def iterations(t: tr.Trace, names: OpNames) -> Dict[str, float]:
    """{family: loop iterations of its runs in the burst}, averaged over the
    devices. Where a run's ops include its loops' condition ops, the
    iterations are their runs (`trace_reduce.loop_iterations` of those ops
    alone, so a condition of several ops counts once); otherwise
    `trace_reduce.loop_iterations` of all its ops."""
    out: Dict[str, float] = defaultdict(float)
    k = max(len(t.planes), 1)
    for plane in t.planes:
        for mod, evs in _module_ops(t, plane):
            name = tr.module_family(mod.name)
            cond = [o.name for o in evs
                    if is_loop_cond(_op_name(names, name, o))]
            out[name] += tr.loop_iterations(
                cond or [o.name for o in evs]) / k
    return dict(out)


def gaps(t: tr.Trace) -> List[Tuple[str, float]]:
    """Every idle stretch of the first device, as (label, ns); the
    stretches are `trace_reduce.Trace.gaps`' own."""
    if not t.planes:
        w0, w1 = t.window()
        return [(t.label, w1 - w0)] if w1 > w0 else []
    plane = t.planes[0]
    busy = tr._merge((o.start_ns, o.end_ns) for o in t.ops(plane))
    w0, w1 = t.window()
    stretches, at = [], w0
    for s, e in busy:
        if s > at:
            stretches.append((at, s))
        at = max(at, e)
    if at < w1:
        stretches.append((at, w1))
    mods = t.modules(plane)
    host = [e for e in t.events if is_host_span(e.name)]
    out = []
    for s, e in stretches:
        mid = (s + e) / 2
        run = [m for m in mods if m.start_ns <= mid <= m.end_ns]
        if run:
            label = f"{tr.module_family(run[0].name)}:in_program"
        else:
            cover = [h for h in host if h.start_ns <= mid <= h.end_ns]
            label = min(cover, key=lambda h: h.dur_ns).name if cover \
                else t.label
        out.append((label, e - s))
    return out


class StageSample:
    """The bursts of one run, read per stage."""

    def __init__(self, traces: Sequence[tr.Trace],
                 names: Optional[OpNames] = None):
        self.traces = list(traces)
        self.names = names or {}
        self.scopes: Dict[str, Dict[str, float]] = defaultdict(
            lambda: defaultdict(float))
        self.iters: Dict[str, float] = defaultdict(float)
        for t in self.traces:
            for fam, by in scope_time(t, self.names).items():
                for scope, ns in by.items():
                    self.scopes[fam][scope] += ns
            for fam, n in iterations(t, self.names).items():
                self.iters[fam] += n

    def op_ns(self, family: str) -> float:
        return sum(self.scopes.get(family, {}).values())

    def us_per_cycle(self, family: str) -> Optional[float]:
        """Device time of the family's programs per loop iteration (one
        simulated cycle of the whole batch), in us; None if no run of the
        family iterated in a burst."""
        n = self.iters.get(family, 0.0)
        return self.op_ns(family) / n / 1e3 if n > 0 else None

    def scope_us_per_cycle(self, family: str, scope: str
                           ) -> Optional[float]:
        """One step scope's share of `us_per_cycle`; None where the
        family's ops carry no step scope (a program built without them)."""
        by = self.scopes.get(family, {})
        per = self.us_per_cycle(family)
        if per is None or not any(s != UNSCOPED for s in by):
            return None
        return per * by.get(scope, 0.0) / self.op_ns(family)

    def unscoped_share(self, family: str) -> Optional[float]:
        total = self.op_ns(family)
        if total <= 0:
            return None
        return self.scopes[family].get(UNSCOPED, 0.0) / total

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The `n` longest idle stretches of any burst: [[label, s], ...]."""
        all_gaps = [g for t in self.traces for g in gaps(t)]
        return [[label, ns / 1e9]
                for label, ns in sorted(all_gaps, key=lambda g: -g[1])[:n]]

    def idle_by_label(self) -> Dict[str, float]:
        """Seconds idle per gap label, over every burst."""
        out: Dict[str, float] = defaultdict(float)
        for t in self.traces:
            for label, ns in gaps(t):
                out[label] += ns / 1e9
        return dict(out)


def harness():
    """The running harness: `run` (imported, as in the tests) or `__main__`
    (`python3 bench/run.py`); None where neither is loaded."""
    for name in ("run", "__main__"):
        mod = sys.modules.get(name)
        if mod is not None and hasattr(mod, "TraceSampler"):
            return mod
    return None


def burst_dir(cell) -> Optional[Path]:
    """Where the running harness's `TraceSampler` wrote the cell's bursts:
    its `CACHE / "trace" / <cell>`."""
    run = harness()
    return None if run is None else Path(run.CACHE) / "trace" / cell.name


# the last run's reading, shared by its readers: (its Sample, the reading)
_LAST: List[Tuple[object, StageSample]] = []


def from_ctx(ctx) -> Optional[StageSample]:
    """The run's bursts, read per stage once for all the readers; None
    when there are none."""
    if _LAST and _LAST[0][0] is ctx["trace"]:
        return _LAST[0][1]
    d = burst_dir(ctx["cell"])
    paths = tr.xplane_paths(str(d)) if d is not None else []
    if not paths:
        return None
    got = [t.label for t in ctx["trace"].traces]
    labels = got if len(got) == len(paths) else ["host"] * len(paths)
    traces = [load_xplane(p, label) for p, label in zip(paths, labels)]
    names: OpNames = {}
    if any(tr.module_family(m.name) == STACKED
           for t in traces for p in t.planes for m in t.modules(p)):
        try:
            names[STACKED] = stacked_op_names(ctx["cell"])
        except Exception as e:      # the scopes go unread; the rest stands
            print(f"[bench] stage_trace: no op_names for {STACKED}: {e!r}",
                  file=sys.stderr)
    sample = StageSample(traces, names)
    _LAST[:] = [(ctx["trace"], sample)]
    return sample
