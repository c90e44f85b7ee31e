"""The plain reference for a sweep, and the comparison that decides `correct`.

The reference is `simref.loop`, a cycle-by-cycle simulation in plain Python
loops written from the simulator's stated semantics, independent of the
program's vectorised step. It runs every row of the sweep (the alone rows,
then the mixes) under every policy, spread over worker processes on the
host, and from those per-row statistics rebuilds what `run_sweep` returns
for each policy with the benchmark's frozen traffic and metric code: the
alone baselines, the per-row metrics with their QoS columns, their
aggregates and the `measured` means.

The comparison is exact for every per-row value and what the host derives
from them in float64: the alone baselines, the per-row metrics and their
aggregates must equal the reference's bit for bit (NaN equals NaN). The
simulator is integer arithmetic plus f32 adds, products and powers of two,
with every ratio correctly rounded, so a sound run on any backend reads
zero mismatches there. The `measured` means are f32 sums over the rows,
whose rounding depends on the order numpy adds them in, and two of their
per-row terms (`energy_bg`, `energy_wake`) are f32 expressions whose
rounding depends on how XLA fuses them: each mean is held to the worst-case
error of such a sum, W * 2**-24 * mean|x| + 2**-24 * |mean| for W rows,
around the float64 mean of the reference's rows. `compare` reports the
largest gap in units of that bound, which a sound run keeps at or below 1.

Control (`control="bf16"`), which must read mismatches: the reference's
statistics rounded to bfloat16, the precision below the f32 the
configuration states.
"""
from __future__ import annotations

import math
import multiprocessing
import os
import sys
from concurrent.futures import ProcessPoolExecutor
from concurrent.futures.process import BrokenProcessPool
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

EXACT_KEYS = ("alone", "rows", "agg", "by_category")
U32 = 2.0 ** -24                        # unit roundoff of float32
TINY32 = 2.0 ** -126                    # smallest normal float32
ROWS_PER_TASK = 8
# below this many simulated row-cycles the reference runs in-process
INLINE_ROW_CYCLES = 200_000


def sim_config(fields: Dict[str, Any]):
    """`simref.params.SimConfig` from a configuration file's fields."""
    from simref import params

    return params.SimConfig(**fields)


def batch(cfg, mixes):
    """(pool, active, alone map) of the sweep's batch: the alone rows, then
    the mixes, built by the benchmark's own traffic code."""
    from simref import workloads as rwl

    apool, aactive, amap = rwl.alone_batch(cfg)
    pool, active = rwl.pool_batch(cfg, mixes)
    return ({k: np.concatenate([apool[k], pool[k]]) for k in pool},
            np.concatenate([aactive, active]), amap)


def workers() -> int:
    """Worker processes for the reference: the cores this process may run
    on but one, at most 12."""
    return max(1, min(12, len(os.sched_getaffinity(0)) - 1))


def raw_stats(fields: Dict[str, Any], policies: Sequence[str], mixes,
              n_cycles: int, warmup: int) -> Dict[str, Dict[str, np.ndarray]]:
    """{policy: {statistic: (rows, ...)}} for every row of the batch."""
    from simref import loop

    pool, active, _ = batch(sim_config(fields), mixes)
    rows = list(range(len(active)))
    chunks = [rows[i:i + ROWS_PER_TASK]
              for i in range(0, len(rows), ROWS_PER_TASK)]
    tasks = [(fields, pol, pool, active, n_cycles, warmup, c)
             for pol in policies for c in chunks]
    n = workers()
    done = None
    if n > 1 and len(policies) * len(rows) * (n_cycles + warmup) \
            >= INLINE_ROW_CYCLES:
        # spawned workers import no JAX and never touch the chip
        try:
            ctx = multiprocessing.get_context("spawn")
            with ProcessPoolExecutor(n, mp_context=ctx) as ex:
                done = list(ex.map(loop.simulate_rows, *zip(*tasks)))
        except (OSError, BrokenProcessPool) as e:
            print(f"[bench] reference workers failed ({e!r}); running the "
                  f"reference in this process", file=sys.stderr, flush=True)
    if done is None:
        done = [loop.simulate_rows(*t) for t in tasks]
    out: Dict[str, List[Dict]] = {p: [] for p in policies}
    for t, res in zip(tasks, done):
        out[t[1]].extend(res)
    return {p: {k: np.stack([r[k] for r in rs]) for k in rs[0]}
            for p, rs in out.items()}


def _to_bf16(m: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    import ml_dtypes

    return {k: v.astype(ml_dtypes.bfloat16).astype(v.dtype)
            if v.dtype.kind == "f" else v for k, v in m.items()}


def assemble(fields: Dict[str, Any], raw: Dict[str, Dict[str, np.ndarray]],
             mixes, control: Optional[str] = None
             ) -> Dict[str, Dict[str, Any]]:
    """What `run_sweep` returns per policy, rebuilt from raw statistics."""
    from simref import metrics as rmet
    from simref import workloads as rwl
    from simref.params import CLS_CPU

    cfg = sim_config(fields)
    _, _, amap = rwl.alone_batch(cfg)
    pool, _ = rwl.pool_batch(cfg, mixes)
    n_alone = len(amap)
    res = {}
    for pol, m in raw.items():
        if control == "bf16":
            m = _to_bf16(m)
        am = {k: v[:n_alone] for k, v in m.items()}
        m = {k: v[n_alone:] for k, v in m.items()}
        alone = rwl.alone_perf_lookup(cfg, am, amap)
        perf = np.where(pool["src_class"] == CLS_CPU, m["ipc"], m["bw"])
        rows = [rmet.workload_metrics(cfg, w, perf[i], alone)
                for i, w in enumerate(mixes)]
        if "lat_hist" in m:
            qb = rmet.qos_breakdown(cfg, m, pool)
            for i, r in enumerate(rows):
                r.update({k: float(v[i]) for k, v in qb.items()})
        x64 = {k: np.asarray(v, np.float64) for k, v in m.items()}
        mean = {k: v.mean(0) for k, v in x64.items()}
        res[pol] = {
            "alone": alone, "rows": rows, "agg": rmet.aggregate(rows),
            "by_category": rmet.by_category(mixes, rows),
            "measured": {k: v.tolist() for k, v in mean.items()},
            "measured_bound": {
                k: (len(v) * U32 * np.abs(v).mean(0)
                    + U32 * np.abs(mean[k])).tolist()
                for k, v in x64.items()},
        }
    return res


def reference_sweep(fields: Dict[str, Any], policies: Sequence[str], mixes,
                    n_cycles: int, warmup: int):
    return assemble(fields, raw_stats(fields, policies, mixes, n_cycles,
                                      warmup), mixes)


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------

def _leaves(x, path: str) -> Iterable[Tuple[str, Any]]:
    if isinstance(x, dict):
        for k in sorted(x):
            yield from _leaves(x[k], f"{path}.{k}")
    elif isinstance(x, (list, tuple)):
        for i, v in enumerate(x):
            yield from _leaves(v, f"{path}[{i}]")
    else:
        yield path, x


def _same(a, b) -> bool:
    a, b = float(a), float(b)
    return (math.isnan(a) and math.isnan(b)) or a == b


def same_tree(a, b) -> bool:
    """Every leaf of `a` equals the leaf of `b` at the same path."""
    la, lb = dict(_leaves(a, "")), dict(_leaves(b, ""))
    return la.keys() == lb.keys() and all(_same(la[k], lb[k]) for k in la)


def compare(program: Dict[str, Dict], ref: Dict[str, Dict]
            ) -> Dict[str, Any]:
    """Compare the program's sweep result with the reference's.

    Returns {"values": values compared, "mismatch": exact values that
    differ, "measured_gap": the largest `measured` gap in units of its
    rounding bound, "by_part": {"policy.part": values that differ},
    "names": the first ones}. A policy missing from the program's result,
    an error entry, and a value present on one side only all count as
    differing, a `measured` value among them. One
    exception: the stacked program pads every policy's statistics to the
    family's union schema, so a `measured` key the per-policy reference
    lacks is padding when every value of it is zero.
    """
    out = {"values": 0, "mismatch": 0, "measured_gap": 0.0, "by_part": {},
           "names": []}

    def miss(part: str, path: str, k: int = 1) -> None:
        out["mismatch"] += k
        out["by_part"][part] = out["by_part"].get(part, 0) + k
        if len(out["names"]) < 20:
            out["names"].append(path)

    for pol, r in ref.items():
        p = program.get(pol)
        rl = dict(_leaves({k: r[k] for k in EXACT_KEYS}, pol))
        rm = dict(_leaves(r["measured"], pol))
        rb = dict(_leaves(r["measured_bound"], pol))
        out["values"] += len(rl) + len(rm)
        if p is None or "error" in p:
            miss(pol, f"{pol}: {'missing' if p is None else p['error'][:200]}",
                 len(rl) + len(rm))
            continue
        pl = dict(_leaves({k: p.get(k) for k in EXACT_KEYS}, pol))
        for path, v in rl.items():
            if path not in pl or not _same(pl[path], v):
                miss(f"{pol}.{path[len(pol) + 1:].split('.')[0].split('[')[0]}",
                     f"{path}: {pl.get(path)!r} != {v!r}")
        for path in pl.keys() - rl.keys():
            miss(f"{pol}.extra", f"{path} (not in the reference)")
        measured = {k: v for k, v in (p.get("measured") or {}).items()
                    if k in r["measured"] or np.any(np.asarray(v) != 0)}
        pm = dict(_leaves(measured, pol))
        for path in pm.keys() ^ rm.keys():
            miss(f"{pol}.measured", f"{path} (on one side only)")
        for path in pm.keys() & rm.keys():
            # a bound of 0 (every row 0) leaves no room: the smallest
            # normal f32 stands in for it, so any difference reads huge
            d = abs(float(pm[path]) - rm[path])
            gap = d / max(rb[path], TINY32)
            out["measured_gap"] = max(out["measured_gap"], gap)
    return out
