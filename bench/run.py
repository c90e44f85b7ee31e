"""Benchmark of the SMS simulator's design sweep on one TPU chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (`BENCHMARK.json` "workloads") is one configuration
(`bench/configs/<name>.json`) under one traffic mix
(`bench/traffic/<name>.json`). A run:

1. set-up: turns on JAX's persistent compilation cache at a fixed directory
   inside the checkout, builds the warm-up sweep's population, and runs one
   whole warm-up sweep at the cell's shapes (trace, compile or cache load,
   run). `setup_s` is the time from the start of this script to the start
   of the window.
2. window: whole sweeps of the program's entry
   `benchmarks.common.run_sweep(cfg, policies, mixes, n_cycles, warmup,
   force=True)`, each in a throwaway results cache, back to back until
   `--seconds` have passed. Sweep k's mixes are drawn from (seed, k), so no
   sweep repeats another's inputs. `cycle_wl_per_s` is every simulated
   cycle-workload of the window's sweeps over the seconds from the first
   sweep's call to the last one's return. With `--trace 1` the profiler
   samples the sweeps after sweep 1 in short bursts (`TraceSampler`), the
   window runs on until it has taken them, and the per-layer metrics are
   read from those and from set-up's compile events instead.
3. check: the pools the program built for every sweep must equal the
   benchmark's own (`simref.workloads`); every policy's result of one
   window sweep drawn from the seed must equal the plain reference's
   (`reference.py`: `simref.loop`, a plain-loop simulation of every row on
   the host's cores), and every window sweep's alone baselines the
   reference's.

The last lines of stderr give each compared number with its limit; the last
line of stdout is the result as one JSON object. Without a TPU, or with
fewer chips than the cell asks for, the run exits with code 3 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, NamedTuple, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"          # compile cache and traces (gitignored)
for p in (str(BENCH), str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import cells  # noqa: E402

EXIT_NO_CHIP = 3
# `measured` means may differ from the reference's by their f32 rounding
# (reference.py); in units of that bound, a sound sum reads at most 1
MEASURED_GAP_LIMIT = 1.0
SETUP_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


def _say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_metric_reader(name: str, root: Path = ROOT):
    """`bench/metrics/<name>.py`'s `read`."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program_mixes(mixes) -> List[Any]:
    """The benchmark's mixes as the program's `Workload` records, `gpu_id`
    as drawn (-1 where the configuration has no GPU)."""
    from repro.core import workloads as wl

    return [wl.Workload(m.category, tuple(int(i) for i in m.cpu_ids),
                        int(m.gpu_id), tuple(int(i) for i in m.hwa_ids))
            for m in mixes]


def traffic_mismatch(cfg, rcfg, mixes) -> int:
    """Elements of the program's sweep and alone pools that differ from the
    pools the benchmark builds from the same mixes with its own tables."""
    import numpy as np
    from repro.core import workloads as wl
    from simref import workloads as rwl

    def diff(a: Dict, b: Dict) -> int:
        n = 0
        for k in a.keys() | b.keys():
            if k not in a or k not in b:
                n += np.size(a.get(k, b.get(k)))
            elif np.shape(a[k]) != np.shape(b[k]):
                n += max(np.size(a[k]), np.size(b[k]))
            else:
                n += int(np.sum(np.asarray(a[k]) != np.asarray(b[k])))
        return n

    pp, pa = wl.pool_batch(cfg, program_mixes(mixes))
    rp, ra = rwl.pool_batch(rcfg, mixes)
    ap, aa, am = wl.alone_batch(cfg)
    bp, ba, bm = rwl.alone_batch(rcfg)
    return (diff(pp, rp) + diff({"a": pa}, {"a": ra}) + diff(ap, bp)
            + diff({"a": aa}, {"a": ba}) + (0 if am == bm else len(bm)))


class JaxProfiler:
    """One profiler session per burst: `jax.profiler` with the Python
    tracer off."""

    def start(self, path: Path) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(path), profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()


class Burst(NamedTuple):
    path: Path                  # the session's directory
    label: str                  # what the main thread was doing at its start
    bin: int
    phase: float                # its start, in sweeps from its sweep's start
    cost_s: float               # the burst and its write


class TraceSampler:
    """Runs the profiler in short bursts over the window sweeps after sweep
    1, each burst its own session under `trace_dir`. A whole sweep runs
    millions of device operations, whose trace takes minutes to write, and
    even a short burst takes longer to write than to run, so the bursts are
    spread over as many sweeps as they need.

    The plan is `BINS` bursts of `BURST_S` seconds, bin b starting b/`BINS`
    of the way into a sweep (sweep 1's length, counted from the running
    sweep's start). Together they cover the whole sweep, and every program
    that runs for a tenth of a sweep or more falls in two bursts or more.
    After a burst and its write, the sampler takes the first bin not yet
    taken that still lies ahead in the running sweep (or began less than a
    quarter of a bin ago), or else the first one left in the next sweep; so
    no two bursts are closer than a burst and its write. It stops once every
    bin is taken, or once `MAX_S` seconds have passed since sweep 2 began;
    `summary` gives the bursts taken beside those planned. `activity` names
    what the main thread is doing, and each burst keeps the activity at its
    start as its label."""

    BINS, BURST_S, MAX_S = 20, 0.12, 150.0

    def __init__(self, trace_dir: Path, profiler=None):
        self.trace_dir = trace_dir
        self.profiler = profiler if profiler is not None else JaxProfiler()
        self.cond = threading.Condition()
        self.stopped = False
        self.activity = "host"
        self.sweep_s = 0.0
        self.starts: List[float] = []     # start of each sweep from sweep 2
        self.bursts: List[Burst] = []
        self.thread: Optional[threading.Thread] = None
        shutil.rmtree(trace_dir, ignore_errors=True)

    def population(self) -> None:
        self.activity = "bench.population"

    def sweep(self, k: int, sweep_s: float) -> None:
        """Called as window sweep `k` begins; `sweep_s` is sweep 1's length.
        The bursts start with sweep 2."""
        with self.cond:
            self.activity = f"bench.sweep {k}"
            if k < 2:
                return
            self.starts.append(time.perf_counter())
            self.cond.notify_all()
        if self.thread is None:
            self.sweep_s = sweep_s
            self.thread = threading.Thread(target=self._run)
            self.thread.start()

    def running(self) -> bool:
        return self.thread is not None and self.thread.is_alive()

    def _wait(self, until, timeout: Optional[float]) -> bool:
        """Waits for `until()` or the timeout; True once stopped."""
        with self.cond:
            self.cond.wait_for(lambda: self.stopped or until(), timeout)
            return self.stopped

    def _run(self) -> None:
        left = list(range(self.BINS))
        t_end = self.starts[0] + self.MAX_S
        while left:
            n = len(self.starts)
            s0 = self.starts[-1]
            now = time.perf_counter()
            w = self.sweep_s / self.BINS
            ahead = [b for b in left if s0 + (b + 0.25) * w >= now]
            if not ahead:                 # to the next sweep
                if self._wait(lambda: len(self.starts) > n,
                              max(t_end - now, 0.0)) or \
                        len(self.starts) == n:
                    return
                continue
            b = ahead[0]
            at = s0 + b * w
            if at > t_end or self._wait(lambda: len(self.starts) > n,
                                        max(at - now, 0.0)):
                return
            if len(self.starts) > n:      # the sweep ended before the bin
                continue
            t0 = time.perf_counter()
            label = self.activity
            d = self.trace_dir / f"burst{len(self.bursts):03d}"
            self.profiler.start(d)
            try:
                self._wait(lambda: False, self.BURST_S)
            finally:
                self.profiler.stop()
            self.bursts.append(Burst(d, label, b, (t0 - s0) / self.sweep_s,
                                     time.perf_counter() - t0))
            left.remove(b)

    def close(self) -> None:
        """Ends the bursts at the window's end at the latest."""
        with self.cond:
            self.stopped = True
            self.cond.notify_all()
        if self.thread is not None:
            self.thread.join()

    def summary(self) -> str:
        cost = sorted(b.cost_s for b in self.bursts)
        spread = f"{cost[0]:.3f}-{cost[-1]:.3f}" if cost else "-"
        size = sum(f.stat().st_size for f in self.trace_dir.rglob("*")
                   if f.is_file())
        return (f"took {len(self.bursts)} of {self.BINS} planned bursts of "
                f"{self.BURST_S} s over {len(self.starts)} sweeps "
                f"({self.BINS - len(self.bursts)} not reached), {size} bytes; "
                f"burst and write {spread} s; bins "
                f"{sorted(b.bin for b in self.bursts)}")

    def sample(self):
        import trace_reduce as tr

        return tr.Sample([tr.load_xplane(p, b.label) for b in self.bursts
                          for p in tr.xplane_paths(str(b.path))])


class Listener:
    """Sums JAX's compile-duration events by kind: set-up's in `total`,
    the window's (which should stay empty) in `window` once `on` is
    cleared."""

    def __init__(self):
        self.on = True
        self.total = dict.fromkeys(SETUP_EVENTS.values(), 0.0)
        self.window = dict.fromkeys(SETUP_EVENTS.values(), 0.0)

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in SETUP_EVENTS:
            (self.total if self.on else self.window)[
                SETUP_EVENTS[event]] += duration


def device_info(devs) -> Dict[str, Any]:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "device_kind": d.device_kind, "count": len(devs)}


def run(cell: cells.Cell, seed: int, seconds: float, trace: bool,
        platform: str = "tpu") -> int:
    """One run of `cell`; returns the exit code. `platform` is "tpu"
    except in the CPU rehearsal tests, which also pass a cut-down cell."""
    (CACHE / "jax").mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    import jax

    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} {platform} "
              f"device(s); JAX found {len(devs)} {devs[0].platform}; "
              f"no fallback", file=sys.stderr)
        return EXIT_NO_CHIP
    listener = Listener()
    jax.monitoring.register_event_duration_secs_listener(listener)

    from repro import compile_cache
    compile_cache.enable()
    from benchmarks import common
    from repro.core import params

    import reference

    f = dict(cell.sim_fields)
    cfg = params.SimConfig(timing=params.Timing(**f.pop("timing")), **f)
    rcfg = reference.sim_config(cell.sim_fields)
    per_sweep = cells.cycle_workloads(cell)

    def sweep(mixes, k: int):
        with jax.profiler.TraceAnnotation(f"bench.sweep {k}"), \
                common.throwaway_cache(prefix="bench_"):
            return common.run_sweep(
                cfg, list(cell.policies), program_mixes(mixes),
                n_cycles=cell.n_cycles, warmup=cell.warmup, tag=cell.name,
                force=True)

    # --- set-up ----------------------------------------------------------
    warm_mixes = cells.population(cell, seed, 0)
    sweep(warm_mixes, 0)
    listener.on = False
    setup_s = time.perf_counter() - T_START
    _say(f"{cell.name}: set-up {setup_s:.3f} s (trace {listener.total}); "
         f"{per_sweep} cycle-workloads per sweep")

    # --- window ----------------------------------------------------------
    trace_dir = CACHE / "trace" / cell.name
    done: List = []                      # (mixes, result) per window sweep
    ends: List[float] = []
    t0 = time.perf_counter()
    sampler = TraceSampler(trace_dir) if trace else None
    while time.perf_counter() - t0 < seconds or (sampler is not None and (
            len(done) < 2 or sampler.running())):
        k = len(done) + 1
        if sampler is not None:
            sampler.population()
        with jax.profiler.TraceAnnotation("bench.population"):
            mixes = cells.population(cell, seed, k)
        if sampler is not None:
            sampler.sweep(k, ends[0] if ends else 0.0)
        done.append((mixes, sweep(mixes, k)))
        ends.append(time.perf_counter() - t0)
    window_s = ends[-1]
    if sampler is not None:
        sampler.close()
        _say(f"{cell.name}: trace {sampler.summary()}")
    stats = devs[0].memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")
    rate = per_sweep * len(done) / window_s
    _say(f"{cell.name}: window {len(done)} sweeps in {window_s:.3f} s "
         f"(ending at {[round(e, 3) for e in ends]}), {rate:.1f} "
         f"cycle-workloads/s; compiled in the window: {listener.window}")

    # --- check -----------------------------------------------------------
    # the pools of every sweep against the benchmark's own; the error
    # entries and alone baselines of every window sweep; and all of one
    # window sweep, drawn from the seed, against the reference
    n_traffic = sum(traffic_mismatch(cfg, rcfg, m)
                    for m in [warm_mixes] + [m for m, _ in done])
    attempted = len(done) * len(cell.policies)
    failed = sum("error" in got.get(p, {"error": ""})
                 for _, got in done for p in cell.policies)
    mixes, got = done[random.Random(seed).randrange(len(done))]
    t_ref = time.perf_counter()
    ref = reference.reference_sweep(cell.sim_fields, cell.policies, mixes,
                                    cell.n_cycles, cell.warmup)
    cmp = reference.compare(got, ref)
    alone_bad = sum(
        not reference.same_tree(other[p]["alone"], ref[p]["alone"])
        for _, other in done for p in cell.policies if "error" not in
        other.get(p, {"error": ""}))
    _say(f"{cell.name}: reference of 1 of {len(done)} sweeps on the host "
         f"CPU {time.perf_counter() - t_ref:.3f} s, {cmp['values']} values "
         f"compared; differing by part: {cmp['by_part']}; alone baselines "
         f"differing in {alone_bad} of {attempted - failed} slices")
    for name in cmp["names"]:
        _say(f"differs: {name}")

    checks = {
        "traffic_mismatch": {"value": n_traffic, "limit": 0},
        "value_mismatch": {"value": cmp["mismatch"] + alone_bad, "limit": 0},
        "measured_gap": {"value": cmp["measured_gap"],
                         "limit": MEASURED_GAP_LIMIT},
        "error_slices": {"value": failed, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and cmp["values"] > 0
    device = {**device_info(devs), "memory_peak_bytes": mem_peak}

    # --- metrics ---------------------------------------------------------
    result: Dict[str, Any] = {"correct": correct, "attempted": attempted,
                              "failed": failed}
    if trace:
        t = sampler.sample()
        ctx = {"trace": t, "cell": cell,
               "setup_compile": dict(listener.total)}
        metrics = {}
        for name, unit in cell.per_layer.items():
            value = load_metric_reader(name, cell.root)(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": unit}
        swept = t.in_sweeps()
        device["busy_s"] = swept.busy_s()
        device["window_s"] = swept.window_s()
        _say(f"{cell.name}: traced {len(t.traces)} bursts, "
             f"{len(swept.traces)} inside a sweep: {device['window_s']:.6f} "
             f"s, busy {device['busy_s']:.6f} s; per family "
             f"{t.family_stats()}; bursts holding each {t.bursts_holding()}")
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": t.top_ops(10),
                               "idle_gaps": t.idle_gaps(10)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"cycle_wl_per_s": rate, "setup_s": setup_s}
        result["metrics"] = {k: {"value": values[k], "unit": u}
                             for k, u in cell.end_to_end.items()}
        result["device"] = device
    for k, c in checks.items():
        _say(f"check {k} = {c['value']} (limit {c['limit']})")
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    return run(cells.load_cell(a.workload), a.seed, a.seconds,
               bool(a.trace))


if __name__ == "__main__":
    sys.exit(main())
