"""Shared benchmark harness for the paper-figure reproductions.

Results are cached as JSON under experiments/sim/ keyed by a config hash, so
``python -m benchmarks.run`` is incremental. Alone-run baselines are cached
separately, keyed by (resolved config, policy, cycles) and independent of
the figure tag, so fig4/fig5/fig7 share them instead of re-simulating.

`run_sweep` dispatches every policy's simulation before converting any
result to numpy: JAX's async dispatch keeps the device busy on later
policies while the host post-processes earlier ones, and an uncached alone
baseline is stacked into the same batch as the workload run (one compile,
one dispatch per policy).

Output convention (per repo contract): ``name,us_per_call,derived`` CSV
rows on stdout.
"""
from __future__ import annotations

import atexit
import collections
import contextlib
import hashlib
import json
import logging
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Deque, Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.core import metrics as met
from repro.core import params
from repro.core import policy as policy_api
from repro.core import simulator as sim
from repro.core import workloads as wl
from repro.core.params import SimConfig

EXP_DIR = Path(__file__).resolve().parents[1] / "experiments" / "sim"
TRACE_DIR = Path(__file__).resolve().parents[1] / "experiments" / "trace"

# Bump when the result schema or the semantics behind cached numbers change
# (new measured columns, metric definition changes, engine behavior fixes).
# The version rides in every cache key — old entries become unreachable —
# AND inside every saved JSON, so `_load_cached`/`evict_stale` can delete
# stale files instead of leaving them to shadow fresh results forever.
CACHE_VERSION = "pr10-telemetry"

# ---------------------------------------------------------------------------
# diagnostics: a leveled logger (REPRO_LOG_LEVEL) + structured sweep spans
# (REPRO_TRACE) replace the old raw [sweep-recover] prints. Both write to
# stderr/files only — the CSV contract on stdout stays machine-parsable.
# ---------------------------------------------------------------------------

LOG = logging.getLogger("repro.bench")
if not LOG.handlers:
    _h = logging.StreamHandler(sys.stderr)
    _h.setFormatter(logging.Formatter("[%(name)s %(levelname)s] %(message)s"))
    LOG.addHandler(_h)
    LOG.propagate = False
LOG.setLevel(os.environ.get("REPRO_LOG_LEVEL", "WARNING").upper())

_TRACE_FILE: Optional[Path] = None


def trace_path() -> Optional[Path]:
    """This process's JSONL trace file (None when REPRO_TRACE=0).

    One file per process under experiments/trace/, named at the first
    record so importing the harness never touches the filesystem.
    """
    global _TRACE_FILE
    if os.environ.get("REPRO_TRACE", "1") == "0":
        return None
    if _TRACE_FILE is None:
        TRACE_DIR.mkdir(parents=True, exist_ok=True)
        _TRACE_FILE = TRACE_DIR / \
            f"trace-{time.strftime('%Y%m%d-%H%M%S')}-{os.getpid()}.jsonl"
    return _TRACE_FILE


class SpanLog:
    """The sweeps' spans and events, kept in memory and written to the JSONL
    trace (`trace_path`) when a root span closes and at exit.

    A record is {"ts", "event", "id", "parent", "sweep_id", **fields}: `ts`
    is the wall-clock start, `parent` the id of the innermost open span,
    `sweep_id` the id of the root span it lies under (a `run_sweep` call's
    `sweep`); a span adds its `dur_s`. A span is also a
    `jax.profiler.TraceAnnotation` of the same name, so in a profiler
    session it lands on the trace's host plane, on the device's clock.
    `records` keeps the latest `KEEP` records for readers in the process,
    whether or not the file is written.
    """

    KEEP = 4096

    def __init__(self):
        self.records: Deque[Dict] = collections.deque(maxlen=self.KEEP)
        self._open: List[Dict] = []
        self._unwritten: List[Dict] = []
        self._next_id = 0

    def event(self, event: str, **fields) -> Dict:
        parent = self._open[-1] if self._open else None
        rec = {"ts": round(time.time(), 6), "event": event,
               "id": self._next_id,
               "parent": parent["id"] if parent else None,
               "sweep_id": parent["sweep_id"] if parent else None,
               **fields}
        self._next_id += 1
        self.records.append(rec)
        if trace_path() is not None:
            self._unwritten.append(rec)
        return rec

    @contextlib.contextmanager
    def span(self, event: str, **fields):
        """Yields the span's record, to which the body may add fields."""
        rec = self.event(event, **fields)
        if rec["sweep_id"] is None:
            rec["sweep_id"] = rec["id"]
        self._open.append(rec)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(event):
                yield rec
        finally:
            rec["dur_s"] = round(time.perf_counter() - t0, 6)
            self._open.pop()
            if not self._open:
                self.flush()

    def flush(self) -> None:
        path = trace_path()
        if path is None or not self._unwritten:
            return
        try:
            with path.open("a") as f:
                f.writelines(json.dumps(r) + "\n" for r in self._unwritten)
        except OSError as e:                 # tracing must never kill a sweep
            LOG.debug("trace write failed: %r", e)
        self._unwritten.clear()


SPANS = SpanLog()
atexit.register(SPANS.flush)


def trace_event(event: str, **fields) -> None:
    """Record one point event under the innermost open span."""
    SPANS.event(event, **fields)


def trace_span(event: str, **fields):
    """Span `event` (`SpanLog.span`) of this process's `SPANS`."""
    return SPANS.span(event, **fields)


def _log_backoff(msg: str) -> None:
    # recovery/degradation breadcrumbs: WARNING level (visible by default)
    # plus a machine-readable degradation-ladder trace event
    LOG.warning("[sweep-recover] %s", msg)
    trace_event("backoff", msg=msg)


def _load_cached(path: Path, force: bool) -> Optional[Dict]:
    """Parsed cache entry, or None. Corrupt and version-stale files are
    EVICTED (deleted) on sight — a stale entry silently shadowing fresh
    semantics is worse than a re-run."""
    if not path.exists():
        return None
    try:
        data = json.loads(path.read_text())
        stale = data.get("cache_version") != CACHE_VERSION
    except (json.JSONDecodeError, OSError):
        data, stale = None, True
    if stale:
        # routine hygiene, not a degradation: INFO level, hidden by default
        LOG.info("evicting stale/corrupt cache entry %s", path.name)
        trace_event("cache_evict", file=path.name)
        path.unlink(missing_ok=True)
        return None
    return None if force else data


@contextlib.contextmanager
def throwaway_cache(prefix: str = "sweep_"):
    """Point the results cache (`EXP_DIR`) at a fresh temporary directory
    for the block, so a forced sweep neither reads nor leaves results in
    experiments/sim/. Yields the temporary directory."""
    global EXP_DIR
    saved = EXP_DIR
    with tempfile.TemporaryDirectory(prefix=prefix) as tmp:
        EXP_DIR = Path(tmp)
        try:
            yield EXP_DIR
        finally:
            EXP_DIR = saved


def evict_stale() -> List[str]:
    """Sweep experiments/sim/ and delete every cache entry whose embedded
    version is not CACHE_VERSION (or that fails to parse). Returns the
    evicted file names."""
    gone = []
    if EXP_DIR.is_dir():
        for path in sorted(EXP_DIR.glob("*.json")):
            if _load_cached(path, force=True) is None and not path.exists():
                gone.append(path.name)
    return gone


def __getattr__(name: str):
    # Full registry sweep (live view: includes variants like sms_dash and
    # any policy registered after import).
    if name == "POLICIES":
        return sim.ALL_POLICIES
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def parity_config(n_cpu: int = 8, n_channels: int = 2, fifo_size: int = 6,
                  dcs_size: int = 4, **kw) -> SimConfig:
    """Centralized buffer sized to SMS entry parity (paper's comparison)."""
    cfg = SimConfig(n_cpu=n_cpu, n_channels=n_channels, fifo_size=fifo_size,
                    dcs_size=dcs_size, **kw)
    entries = cfg.n_src * cfg.fifo_size + cfg.n_banks * cfg.dcs_size
    return cfg.replace(buf_entries=entries)


def resolved_config(cfg: SimConfig, policy: str) -> SimConfig:
    """The config the simulator actually runs: after `policy.configure`."""
    return policy_api.get(policy).configure(cfg)


def resolved_knobs(cfg: SimConfig, policy: str) -> Dict[str, object]:
    """Host-side view of the knob point the policy actually runs at (after
    `configure_knobs` — e.g. sms_dash pins dash=True). Part of every cache
    key: knob variants of one policy may never share a cache entry."""
    rcfg = resolved_config(cfg, policy)
    kn = policy_api.resolve_knobs(rcfg, policy_api.get(policy))
    return {f: np.asarray(getattr(kn, f)).item()
            for f in params.KNOB_FIELDS}


def _key(cfg: SimConfig, policy: str, tag: str, n_cycles: int,
         warmup: int, seed: int, n_per_cat: int) -> str:
    # hash the RESOLVED config AND knob point: a variant policy (e.g.
    # sms_dash, whose configure_knobs pins dash=True) can never collide
    # with its base under any cache-sharing scheme
    blob = json.dumps([CACHE_VERSION, repr(resolved_config(cfg, policy)),
                       sorted(resolved_knobs(cfg, policy).items()),
                       policy, tag, n_cycles, warmup, seed, n_per_cat],
                      sort_keys=True)
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


def _alone_key(cfg: SimConfig, policy: str, n_cycles: int,
               warmup: int) -> str:
    blob = json.dumps([CACHE_VERSION, repr(resolved_config(cfg, policy)),
                       sorted(resolved_knobs(cfg, policy).items()),
                       policy, n_cycles, warmup], sort_keys=True)
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


def _load_alone(cfg: SimConfig, policy: str, n_cycles: int, warmup: int,
                force: bool) -> Optional[Dict[str, float]]:
    path = EXP_DIR / \
        f"alone_{policy}_{_alone_key(cfg, policy, n_cycles, warmup)}.json"
    data = _load_cached(path, force)
    return None if data is None else data["alone"]


def _save_alone(cfg: SimConfig, policy: str, n_cycles: int, warmup: int,
                alone: Dict[str, float]) -> None:
    path = EXP_DIR / \
        f"alone_{policy}_{_alone_key(cfg, policy, n_cycles, warmup)}.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps({"cache_version": CACHE_VERSION,
                                "alone": alone}, indent=1))


def _stacked_fetch(dev, idx: int, box: Dict):
    """Deferred (W, S) metric slice for policy `idx` of a stacked dispatch.

    The first fetch of the group blocks on the shared device result and
    converts it to numpy ONCE (cached in `box`); siblings reuse the host
    copy instead of re-transferring the whole (W, P, S) stack.
    """
    def fetch() -> Dict[str, np.ndarray]:
        if "m" not in box:
            box["m"] = {k: np.asarray(v) for k, v in dev.items()}
        return {k: v[:, idx] for k, v in box["m"].items()}
    return fetch


def _chunked_run(cfg: SimConfig, polname: str, point: Optional[Dict],
                 batch_pool: Dict[str, np.ndarray],
                 batch_active: np.ndarray, n_cycles: int,
                 warmup: int) -> Dict[str, np.ndarray]:
    """Last rung of the degradation ladder: run the batch one workload row
    at a time (same compiled program reused across rows) and concatenate.
    Isolates a poisoned row — every healthy row still yields its metrics.
    `point` carries value-knob overrides for grid slices (None = defaults).
    """
    W = batch_active.shape[0]
    outs = []
    for i in range(W):
        row_pool = {k: v[i:i + 1] for k, v in batch_pool.items()}
        row_act = batch_active[i:i + 1]
        if point is None:
            m = sim.simulate(cfg, polname, row_pool, row_act, n_cycles,
                             warmup)
            outs.append({k: np.asarray(v) for k, v in m.items()})
        else:
            m = sim.simulate_grid(cfg, polname, [point], row_pool, row_act,
                                  n_cycles, warmup)
            outs.append({k: np.asarray(v)[:, 0] for k, v in m.items()})
    return {k: np.concatenate([o[k] for o in outs]) for k in outs[0]}


def _fetch_recover(cfg: SimConfig, polname: str, label: str,
                   point: Optional[Dict], fetch,
                   batch_pool: Dict[str, np.ndarray],
                   batch_active: np.ndarray, n_cycles: int, warmup: int,
                   strict: bool) -> Dict[str, np.ndarray]:
    """Degradation ladder below the (possibly shared) async fetch: retry
    the slice as its own synchronous dispatch, then one workload row at a
    time. `strict` re-raises at the first failure instead of degrading.
    `fetch=None` means the dispatch itself already failed upstream."""
    if fetch is not None:
        try:
            return fetch()
        except Exception as e:
            if strict:
                raise
            _log_backoff(f"{label}: batched fetch failed ({e!r}); "
                         f"retrying as a solo dispatch")
    try:
        if point is None:
            m = sim.simulate(cfg, polname, batch_pool, batch_active,
                             n_cycles, warmup)
            return {k: np.asarray(v) for k, v in m.items()}
        m = sim.simulate_grid(cfg, polname, [point], batch_pool,
                              batch_active, n_cycles, warmup)
        return {k: np.asarray(v)[:, 0] for k, v in m.items()}
    except Exception as e:
        if strict:
            raise
        _log_backoff(f"{label}: solo dispatch failed ({e!r}); "
                     f"retrying per-workload chunks")
    return _chunked_run(cfg, polname, point, batch_pool, batch_active,
                        n_cycles, warmup)


def run_sweep(cfg: SimConfig, policies: Sequence[str],
              workloads: Sequence[wl.Workload], n_cycles: int = 16_000,
              warmup: int = 2_000, seed: int = 7, tag: str = "",
              force: bool = False, stacked: bool = True,
              strict: bool = False) -> Dict[str, Dict]:
    """Alone-normalized per-workload metrics for each policy (cached).

    Uncached policies that opt into the stacked execution path (the
    `CentralizedPolicy` family — see `sim.stackable_names`) run as ONE
    stacked dispatch: their states ride a leading policy axis through a
    single scan, so the whole family costs one trace+compile instead of one
    per policy. The rest (SMS-style protocols, configured variants) keep
    the per-policy path, async-dispatched before any result is blocked on.
    A policy whose alone baseline is uncached gets the alone rows stacked
    into the same batch as the workload rows: one compile + one dispatch
    either way. `stacked=False` forces the per-policy path everywhere
    (benchmarks/simspeed.py uses it to measure the stacking win).

    Fault tolerance: a failing slice degrades down a logged ladder —
    stacked batch halved recursively, then per-policy dispatch, then
    per-workload chunks — and, if everything fails, lands in the result
    dict as ``{"policy": ..., "error": ...}`` (never cached, so a re-run
    retries it) while every healthy slice is persisted per-slice as it
    completes. `strict=True` re-raises at the first failure instead.

    Spans (`trace_span`): `sweep` holds the call, and under it
    `sweep.pools` (the alone and mix pools), one `sweep.dispatch` per
    program, and per policy `sweep.fetch` (wait for the device and copy
    back) and `sweep.rows` (per-row metrics, aggregates, the cache write).
    """
    with trace_span("sweep", tag=tag or "std", policies=list(policies),
                    n_workloads=len(workloads), n_cycles=n_cycles) as span:
        results = _run_sweep(cfg, policies, workloads, n_cycles, warmup,
                             seed, tag, force, stacked, strict)
        span["errors"] = [p for p, r in results.items() if "error" in r]
    return results


def _run_sweep(cfg, policies, workloads, n_cycles, warmup, seed, tag, force,
               stacked, strict) -> Dict[str, Dict]:
    with trace_span("sweep.pools"):
        apool, aactive, amap = wl.alone_batch(cfg)
        pool, active = wl.pool_batch(cfg, workloads)
    n_alone = len(amap)
    results: Dict[str, Dict] = {}
    todo = []
    for pol in policies:
        key = _key(cfg, pol, tag or "std", n_cycles, warmup, seed,
                   len(workloads))
        path = EXP_DIR / f"{pol}_{key}.json"
        cached = _load_cached(path, force)
        if cached is not None:
            trace_event("cache_hit", policy=pol, file=path.name)
            results[pol] = cached
            continue
        todo.append((pol, path, _load_alone(cfg, pol, n_cycles, warmup,
                                            force)))

    stackset = set(sim.stackable_names(cfg, [p for p, _, _ in todo])) \
        if stacked else set()
    # group stackable policies by batch composition (alone rows stacked in
    # or not); a group of one has no compile to amortize — per-policy path
    groups: Dict[bool, list] = {}
    singles = []
    for item in todo:
        if item[0] in stackset:
            groups.setdefault(item[2] is None, []).append(item)
        else:
            singles.append(item)
    for need_alone in list(groups):
        if len(groups[need_alone]) == 1:
            singles.extend(groups.pop(need_alone))

    def batch_for(need_alone):
        if need_alone:
            return ({k: np.concatenate([apool[k], pool[k]]) for k in pool},
                    np.concatenate([aactive, active]))
        return pool, active

    pending = []                # (pol, path, alone, fetch, bpool, bactive)

    def solo_dispatch(item):
        pol, path, alone = item
        bp, ba = batch_for(alone is None)
        try:
            # the async-dispatch span covers trace + compile + enqueue
            with trace_span("sweep.dispatch", policy=pol, stacked=False):
                dev = sim.simulate_async(cfg, pol, bp, ba, n_cycles, warmup)
            fetch = lambda dev=dev: {k: np.asarray(v)
                                     for k, v in dev.items()}
        except Exception as e:
            if strict:
                raise
            _log_backoff(f"{pol}: async dispatch failed ({e!r}); "
                         f"deferring to the sync fallback ladder")
            fetch = None
        pending.append((pol, path, alone, fetch, bp, ba))

    def stacked_dispatch(items, need_alone):
        # ladder rung 1: a failing stacked trace/compile halves the batch
        # recursively until the culprit is isolated on the solo path
        if len(items) == 1:
            solo_dispatch(items[0])
            return
        bp, ba = batch_for(need_alone)
        try:
            names = [p for p, _, _ in items]
            with trace_span("sweep.dispatch", policies=names,
                            stacked=True):
                dev = sim.simulate_stacked_async(
                    cfg, tuple(names), bp, ba, n_cycles, warmup)
        except Exception as e:
            if strict:
                raise
            h = len(items) // 2
            _log_backoff(
                f"stacked dispatch {[p for p, _, _ in items]} failed "
                f"({e!r}); halving to {h}+{len(items) - h}")
            stacked_dispatch(items[:h], need_alone)
            stacked_dispatch(items[h:], need_alone)
            return
        box: Dict = {}
        for idx, (pol, path, alone) in enumerate(items):
            pending.append((pol, path, alone,
                            _stacked_fetch(dev, idx, box), bp, ba))

    for need_alone, items in groups.items():
        stacked_dispatch(items, need_alone)
    for item in singles:
        solo_dispatch(item)
    for pol, path, alone, fetch, bp, ba in pending:
        # elapsed_s = this policy's block + post-process segment only; the
        # dispatch/compile phase overlaps across policies and is reported
        # by benchmarks/simspeed.py as sweep wall-clock
        t0 = time.time()
        try:
            with trace_span("sweep.fetch", policy=pol):
                m = _fetch_recover(cfg, pol, pol, None, fetch, bp, ba,
                                   n_cycles, warmup, strict)
        except Exception as e:
            if strict:
                raise
            _log_backoff(f"{pol}: ladder exhausted ({e!r}); "
                         f"recording error entry (not cached)")
            results[pol] = {"policy": pol, "error": repr(e)}
            continue
        with trace_span("sweep.rows", policy=pol):
            if alone is None:
                am = {k: v[:n_alone] for k, v in m.items()}
                m = {k: v[n_alone:] for k, v in m.items()}
                alone = wl.alone_perf_lookup(cfg, am, amap)
                _save_alone(cfg, pol, n_cycles, warmup, alone)
                trace_event("alone_baseline", policy=pol, n_rows=n_alone)
            perf = sim.perf_vector(cfg, m, pool)
            rows = [met.workload_metrics(cfg, w, perf[i], alone)
                    for i, w in enumerate(workloads)]
            if "lat_hist" in m:
                # per-class QoS columns (tail latency, deadline-met rate) join
                # the speedup/fairness rows, so agg/by_category cover them too
                qb = met.qos_breakdown(cfg, m, pool)
                for i, r in enumerate(rows):
                    r.update({k: float(v[i]) for k, v in qb.items()})
            out = {
                "policy": pol,
                "cache_version": CACHE_VERSION,
                "elapsed_s": round(time.time() - t0, 1),
                "alone": alone,
                "rows": rows,
                "categories": [w.category for w in workloads],
                "agg": met.aggregate(rows),
                "by_category": met.by_category(workloads, rows),
                "measured": {k: np.asarray(v).mean(0).tolist()
                             for k, v in m.items()},
            }
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text(json.dumps(out, indent=1))
        results[pol] = out
    return {pol: results[pol] for pol in policies}


def run_policy(cfg: SimConfig, policy: str, workloads: Sequence[wl.Workload],
               n_cycles: int = 16_000, warmup: int = 2_000, seed: int = 7,
               tag: str = "", force: bool = False) -> Dict:
    """Alone-normalized per-workload metrics for one policy (cached)."""
    return run_sweep(cfg, [policy], workloads, n_cycles=n_cycles,
                     warmup=warmup, seed=seed, tag=tag, force=force)[policy]


def _grid_key(cfg: SimConfig, policy: str, overrides: Dict, tag: str,
              n_cycles: int, warmup: int, seed: int, n_wl: int) -> str:
    blob = json.dumps([CACHE_VERSION, repr(resolved_config(cfg, policy)),
                       sorted(resolved_knobs(cfg, policy).items()),
                       policy, sorted(overrides.items()), tag,
                       n_cycles, warmup, seed, n_wl],
                      sort_keys=True, default=str)
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


def run_grid(cfg: SimConfig, specs: Sequence, workloads: Sequence[wl.Workload],
             n_cycles: int = 16_000, warmup: int = 2_000, seed: int = 7,
             tag: str = "grid", force: bool = False,
             strict: bool = False) -> Dict[str, Dict]:
    """Alone-normalized metrics for a (policy x knob-variant) grid (cached).

    `specs` is a sequence of (policy, label, knob_overrides) triples;
    overrides may mix value-like and period-like knobs. Uncached stackable
    specs run as ONE stacked-grid dispatch (policy and knob variants share
    the leading slice axis — one XLA program for the whole grid); the
    non-stackable rest (the SMS family) groups per (policy, period
    overrides) with value-knob variants on a vmapped knob axis — one
    compiled program per group instead of one per point. Alone-baseline
    rows ride the same batch, so every variant slice gets an alone
    normalization measured at its own knob point.

    Returns {label: result}, parallel to specs; labels must be unique.
    Failing slices degrade down the same logged ladder as `run_sweep`
    (halve the stacked grid, solo dispatch, per-workload chunks) and end
    as uncached ``{"policy", "label", "error"}`` entries unless
    `strict=True`, which re-raises at the first failure.
    """
    specs = [(p, lab, dict(ov)) for p, lab, ov in specs]
    labels = [lab for _, lab, _ in specs]
    if len(set(labels)) != len(labels):
        raise ValueError("duplicate run_grid labels")
    apool, aactive, amap = wl.alone_batch(cfg)
    n_alone = len(amap)
    pool, active = wl.pool_batch(cfg, workloads)
    batch_pool = {k: np.concatenate([apool[k], pool[k]]) for k in pool}
    batch_active = np.concatenate([aactive, active])

    results: Dict[str, Dict] = {}
    todo = []
    for polname, label, ov in specs:
        key = _grid_key(cfg, polname, ov, tag, n_cycles, warmup, seed,
                        len(workloads))
        path = EXP_DIR / f"grid_{polname}_{key}.json"
        cached = _load_cached(path, force)
        if cached is not None:
            trace_event("cache_hit", policy=polname, label=label,
                        file=path.name)
            results[label] = cached
        else:
            todo.append((polname, label, ov, path))

    def _stackable(item):
        per, _ = params.split_overrides(item[2])
        return policy_api.is_stackable(item[0], cfg.replace(**per))

    stacked_items = [it for it in todo if _stackable(it)]
    singles = [it for it in todo if not _stackable(it)]
    pending = []

    def stacked_dispatch(items):
        if len(items) == 1:
            singles.append(items[0])
            return
        try:
            with trace_span("sweep.dispatch", stacked=True, grid=True,
                            labels=[it[1] for it in items]):
                dev = sim.simulate_stacked_grid_async(
                    cfg, [(p, ov) for p, _, ov, _ in items],
                    batch_pool, batch_active, n_cycles, warmup)
        except Exception as e:
            if strict:
                raise
            h = len(items) // 2
            _log_backoff(
                f"stacked grid dispatch {[it[1] for it in items]} failed "
                f"({e!r}); halving to {h}+{len(items) - h}")
            stacked_dispatch(items[:h])
            stacked_dispatch(items[h:])
            return
        box: Dict = {}
        for idx, it in enumerate(items):
            pending.append((it, _stacked_fetch(dev, idx, box)))

    if len(stacked_items) >= 2:
        stacked_dispatch(stacked_items)
    else:
        singles = stacked_items + singles
    by_group: Dict[tuple, list] = {}
    for it in singles:
        per, _ = params.split_overrides(it[2])
        by_group.setdefault((it[0], tuple(sorted(per.items()))),
                            []).append(it)
    for (polname, per), items in by_group.items():
        gcfg = cfg.replace(**dict(per))
        points = [params.split_overrides(it[2])[1] for it in items]
        try:
            with trace_span("sweep.dispatch", policy=polname, grid=True,
                            labels=[it[1] for it in items]):
                dev = sim.simulate_grid_async(gcfg, polname, points,
                                              batch_pool, batch_active,
                                              n_cycles, warmup)
            box = {}
            for idx, it in enumerate(items):
                pending.append((it, _stacked_fetch(dev, idx, box)))
        except Exception as e:
            if strict:
                raise
            _log_backoff(f"grid group {[it[1] for it in items]} dispatch "
                         f"failed ({e!r}); deferring to the fallback "
                         f"ladder")
            pending.extend((it, None) for it in items)

    for (polname, label, ov, path), fetch in pending:
        t0 = time.time()
        per, point = params.split_overrides(ov)
        try:
            with trace_span("sweep.fetch", policy=polname, label=label):
                m = _fetch_recover(cfg.replace(**per), polname, label,
                                   point, fetch, batch_pool, batch_active,
                                   n_cycles, warmup, strict)
        except Exception as e:
            if strict:
                raise
            _log_backoff(f"{label}: ladder exhausted ({e!r}); "
                         f"recording error entry (not cached)")
            results[label] = {"policy": polname, "label": label,
                              "error": repr(e)}
            continue
        am = {k: v[:n_alone] for k, v in m.items()}
        m = {k: v[n_alone:] for k, v in m.items()}
        alone = wl.alone_perf_lookup(cfg, am, amap)
        perf = sim.perf_vector(cfg, m, pool)
        rows = [met.workload_metrics(cfg, w, perf[i], alone)
                for i, w in enumerate(workloads)]
        if "lat_hist" in m:
            qb = met.qos_breakdown(cfg, m, pool)
            for i, r in enumerate(rows):
                r.update({k: float(v[i]) for k, v in qb.items()})
        out = {
            "policy": polname,
            "label": label,
            "overrides": ov,
            "cache_version": CACHE_VERSION,
            "elapsed_s": round(time.time() - t0, 1),
            "alone": alone,
            "rows": rows,
            "categories": [w.category for w in workloads],
            "agg": met.aggregate(rows),
            "by_category": met.by_category(workloads, rows),
            "measured": {k: np.asarray(v).mean(0).tolist()
                         for k, v in m.items()},
        }
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(out, indent=1))
        results[label] = out
    return {lab: results[lab] for _, lab, _ in specs}


def emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}")


def fmt_cat_table(results: Dict[str, Dict], metric: str) -> str:
    cats = list(wl.CATEGORIES)
    lines = ["policy," + ",".join(cats) + ",avg"]
    for pol, res in results.items():
        if "error" in res:
            # tolerant-mode failure entry: keep the row so the partial
            # report stays parallel to the request, but mark it plainly
            lines.append(pol + ",ERROR:" + res["error"].replace(",", ";"))
            continue
        vals = [res["by_category"].get(c, {}).get(metric, float("nan"))
                for c in cats]
        lines.append(pol + "," + ",".join(f"{v:.3f}" for v in vals) +
                     f",{res['agg'][metric]:.3f}")
    return "\n".join(lines)
