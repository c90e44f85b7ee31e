"""System metrics: weighted speedup, max slowdown, harmonic speedup (§5),
per-class QoS (deadline-met rate, tail latency, class-masked fairness).

The benchmark's copy of the program's metric arithmetic, as it stood when
the benchmark was defined: the reference's per-row statistics reduce to
what `run_sweep` reports through these, and no later change to the
program moves them."""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from simref.params import (CLASS_NAMES, CLS_CPU, CLS_GPU, CLS_HWA,
                               SimConfig)
from simref.workloads import CPU_BENCH, GPU_BENCH, HWA_BENCH, Workload


def max_slowdown(slowdowns: np.ndarray,
                 mask: Optional[np.ndarray] = None) -> float:
    """The unfairness reduction, shared by every per-class variant: max
    slowdown over the (optionally class-masked) sources. NaN when the mask
    selects nothing, so an absent class can't fake perfect fairness."""
    s = np.asarray(slowdowns, np.float64)
    if mask is not None:
        mask = np.asarray(mask, bool)
        if not mask.any():
            return float("nan")
        s = s[mask]
    return float(s.max())


def per_source_alone(cfg: SimConfig, wl: Workload,
                     alone: Dict[str, float]) -> np.ndarray:
    """Alone performance vector (S,) for one workload."""
    out = np.ones((cfg.n_src,), np.float64)
    for i, b in enumerate(wl.cpu_ids[:cfg.n_cpu]):
        out[i] = max(alone[CPU_BENCH[b][0]], 1e-9)
    if cfg.n_gpu > 0:
        out[cfg.n_cpu] = max(alone[GPU_BENCH[wl.gpu_id][0]], 1e-9)
    for j, b in enumerate(wl.hwa_ids[:cfg.n_hwa]):
        out[cfg.n_cpu + cfg.n_gpu + j] = max(alone[HWA_BENCH[b][0]], 1e-9)
    return out


def workload_metrics(cfg: SimConfig, wl: Workload, shared_perf: np.ndarray,
                     alone: Dict[str, float]) -> Dict[str, float]:
    """shared_perf: (S,) per-source perf (IPC for CPUs, BW for GPU/HWAs).

    The populated sources are the n_cpu CPUs, the GPU at index n_cpu where
    the configuration has one, and the workload's HWAs; slowdown reductions
    run over exactly those, with the per-class variants masking the shared
    `max_slowdown` reduction. `weighted_speedup` keeps its 2-class CPU+GPU
    definition (the paper's headline metric), the CPUs' alone with no GPU;
    HWA throughput reports separately as `hwa_speedup`. An absent class's
    columns are left out (`gpu_speedup`, `hwa_*`).
    """
    alone_v = per_source_alone(cfg, wl, alone)
    ratio = np.maximum(shared_perf, 1e-9) / alone_v
    n = cfg.n_cpu
    gpu = [n] if cfg.n_gpu > 0 else []
    n_hwa = len(wl.hwa_ids[:cfg.n_hwa])
    idx = np.asarray(list(range(n)) + gpu +
                     [n + cfg.n_gpu + j for j in range(n_hwa)])
    cls = np.asarray([CLS_CPU] * n + [CLS_GPU] * len(gpu)
                     + [CLS_HWA] * n_hwa)
    slowdowns = 1.0 / np.maximum(ratio[idx], 1e-9)
    cpu_ws = float(ratio[:n].sum())
    out = {"weighted_speedup": cpu_ws, "cpu_weighted_speedup": cpu_ws}
    if gpu:
        gpu_su = float(ratio[n])
        out["weighted_speedup"] = cpu_ws + gpu_su
        out["gpu_speedup"] = gpu_su
    out.update({
        "max_slowdown": max_slowdown(slowdowns),
        "cpu_max_slowdown": max_slowdown(slowdowns, cls == CLS_CPU),
        "harmonic_speedup": float(len(idx) / (1.0 / ratio[idx]).sum()),
    })
    if n_hwa:
        out["hwa_speedup"] = float(ratio[idx[cls == CLS_HWA]].sum())
        out["hwa_max_slowdown"] = max_slowdown(slowdowns, cls == CLS_HWA)
    return out


def hist_quantile(hist: np.ndarray, edges: np.ndarray, q: float
                  ) -> np.ndarray:
    """Quantile(s) from latency histograms: (..., BINS) counts -> (...,)
    upper-edge latency of the bin where the cumulative mass crosses q.
    Rows with no mass report 0."""
    h = np.asarray(hist, np.float64)
    tot = h.sum(-1)
    cum = np.cumsum(h, -1)
    idx = np.argmax(cum >= q * np.maximum(tot, 1e-9)[..., None], axis=-1)
    return np.where(tot > 0, np.asarray(edges, np.float64)[idx], 0.0)


def qos_breakdown(cfg: SimConfig, m: Dict[str, np.ndarray],
                  pool_batch: Dict[str, np.ndarray],
                  quantiles: Sequence[float] = (0.95, 0.99)
                  ) -> Dict[str, np.ndarray]:
    """Per-workload (W,) QoS metrics from `simulate` outputs.

    Per-class tail latency comes from the issue-time latency histogram
    (`lat_hist`, needs cfg.qos_enabled): source rows roll up to classes by
    masking with `src_class`, then the pooled histogram reduces to p95/p99.
    Frame-deadline accounting (HWA class): deadline-met rate over the
    frames the measurement window released.
    """
    cls = np.asarray(pool_batch["src_class"])                  # (W, S)
    hist = np.asarray(m["lat_hist"], np.float64)               # (W, S, B)
    edges = (np.arange(cfg.lat_bins, dtype=np.float64) + 1.0) \
        * cfg.lat_bin_width
    out: Dict[str, np.ndarray] = {}
    for k, kname in enumerate(CLASS_NAMES):
        pooled = np.where((cls == k)[..., None], hist, 0.0).sum(-2)
        for q in quantiles:
            out[f"lat_p{int(round(q * 100))}_{kname}"] = \
                hist_quantile(pooled, edges, q)
    hwa = cls == CLS_HWA
    rel = np.where(hwa, np.asarray(m["frames_released"], np.float64),
                   0.0).sum(-1)
    met = np.where(hwa, np.asarray(m["dl_met"], np.float64), 0.0).sum(-1)
    out["frames_released"] = rel
    out["dl_met_rate"] = met / np.maximum(rel, 1.0)
    return out


def aggregate(rows: Sequence[Dict[str, float]]) -> Dict[str, float]:
    keys = rows[0].keys()
    return {k: float(np.mean([r[k] for r in rows])) for k in keys}


def by_category(workloads: Sequence[Workload],
                rows: Sequence[Dict[str, float]]):
    cats: Dict[str, List[Dict[str, float]]] = {}
    for wl, r in zip(workloads, rows):
        cats.setdefault(wl.category, []).append(r)
    return {c: aggregate(rs) for c, rs in cats.items()}
