"""Simulation drivers: scan over cycles, vmap over workloads, metrics.

``simulate(cfg, policy, pool_batch, active_batch, n_cycles, warmup)`` runs a
batch of workloads through one scheduler and returns per-source measured
metrics. Stats are delta-measured after a warmup period.

Policies resolve by name through `repro.core.policy.POLICY_REGISTRY`; the
drivers are generic over the `MemoryPolicy` protocol, so a newly registered
policy is immediately simulatable (and appears in `ALL_POLICIES`) with no
changes here.
"""
from __future__ import annotations

import functools
import warnings
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core import params
from repro.core import policy as policy_api
from repro.core.params import SimConfig, SourcePool

_SNAP_KEYS = ("insts_done", "emitted", "completed", "sum_lat", "dl_met",
              "dl_missed", "frames_released")
_DRAM_SNAP = ("hits", "issued")
# energy accumulators are delta-measured like the service stats; present in
# dram_state only when cfg.energy_enabled (checked against the live tree)
_ENERGY_SNAP = ("e_act", "e_rw", "sb_cycles", "e_wake", "pd_cycles")
# QoS latency histogram, present only when cfg.qos_enabled
_QOS_SNAP = ("lat_hist",)
# policy QoS counters surfaced from scheduler state when present (the
# stacked union schema gives every slice the key; zeros for policies
# without the counter)
_SCHED_SNAP = {"sq_urgent_adm": "urgent_admits"}


def __getattr__(name: str):
    # Live registry enumerations (PEP 562), in registration order, so a
    # policy registered at runtime appears immediately. POLICIES is the
    # baseline sweep (no configured variants); ALL_POLICIES adds the
    # variants, e.g. sms_dash.
    if name == "POLICIES":
        return policy_api.baseline_names()
    if name == "ALL_POLICIES":
        return policy_api.names()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

def _init(cfg: SimConfig, policy: str, knobs=None):
    """Resolve the policy and build (bound cfg, policy object, carry).

    The carry holds only cycle-varying state; read-only workload parameters
    (pool, active) are closed over in `policy.make_step`. The returned cfg
    is a `params.BoundConfig`: shapes/periods stay trace-time Python values
    while value-like knobs come from `knobs` (default: cfg's own values,
    filtered through the policy's `configure_knobs`) — possibly traced
    arrays riding a vmapped variant axis.
    """
    pol = policy_api.get(policy)
    cfg = pol.configure(cfg)
    kn = policy_api.resolve_knobs(cfg, pol, knobs)
    carry = (engine.source_state(cfg), pol.init_state(cfg),
             engine.dram_state(cfg))
    return params.bind(cfg, kn), pol, carry


def _run_cycles(step, skip_body, carry, t0: int, t1: int, unroll: int):
    """Run cycles [t0, t1) — the ONE driver loop every `simulate*` variant
    routes through.

    Ticked mode (skip_body None): the chunked `lax.scan` over every cycle.
    Skipping mode: a `lax.while_loop` whose body processes one cycle and
    jumps `t` to the next-event witness (clamped to t1, so snapshot
    boundaries land exactly where the ticked driver takes them). Under
    `vmap` the while_loop batches per element — finished workloads freeze
    while stragglers run on — so the vmap/stacked structure is unchanged.

    Returns (carry, steps): steps counts processed cycles (== t1 - t0 when
    ticked, a traced scalar when skipping).
    """
    if skip_body is None:
        carry, _ = jax.lax.scan(step, carry, jnp.arange(t0, t1),
                                unroll=unroll)
        return carry, jnp.int32(t1 - t0)

    def body(state):
        carry, t, n = state
        carry, t_new = skip_body(carry, t, jnp.int32(t1))
        return carry, t_new, n + 1

    carry, _, steps = jax.lax.while_loop(
        lambda s: s[1] < t1, body, (carry, jnp.int32(t0), jnp.int32(0)))
    return carry, steps


def div_rn(a: jax.Array, b) -> jax.Array:
    """f32 `a / b` rounded to nearest-even, bit-identical on every backend.

    XLA:CPU divides as IEEE does; the TPU's f32 divide is not correctly
    rounded (on a v5e, `avg_lat` and `rbl` taken with `/` differed from the
    CPU's in some elements). Here the 24-bit significands are divided by
    integer long division into 27 quotient bits plus a sticky bit, which
    one exact-operand f32 add rounds once; the exponent is applied by exact
    power-of-two products. For finite a >= 0 and b > 0 whose quotient is a
    normal f32 or zero (a zero or subnormal `a` gives 0).
    """
    a = jnp.asarray(a, jnp.float32)
    b = jnp.broadcast_to(jnp.asarray(b, jnp.float32), a.shape)
    ia = jax.lax.bitcast_convert_type(a, jnp.int32)
    ib = jax.lax.bitcast_convert_type(b, jnp.int32)
    ea, eb = ia >> 23, ib >> 23
    ma = (ia & 0x7FFFFF) | 0x800000
    mb = (ib & 0x7FFFFF) | 0x800000
    n, r = jnp.zeros_like(ma), ma
    for _ in range(27):       # n = floor(ma / mb * 2**26), in [2**25, 2**27)
        bit = r >= mb
        n = 2 * n + bit.astype(jnp.int32)
        r = 2 * jnp.where(bit, r - mb, r)
    n = n | (r != 0).astype(jnp.int32)    # sticky bit below the guard bit
    q = (n >> 16).astype(jnp.float32) * jnp.float32(65536.0) \
        + (n & 0xFFFF).astype(jnp.float32)
    pow2 = lambda e: jax.lax.bitcast_convert_type(
        (jnp.clip(e, -126, 127) + 127) << 23, jnp.float32)
    e = ea - eb - 26
    q = q * pow2(e >> 1) * pow2(e - (e >> 1))
    return jnp.where(ea == 0, jnp.float32(0.0), q)


def _scan_and_measure(cfg: SimConfig, step, skip_body, carry, n_cycles: int,
                      warmup: int, unroll: int) -> Dict[str, jax.Array]:
    """Warmup run, stat snapshot, measured run, delta metrics.

    Generic over the carry's leading axes: works for the per-policy step
    ((S,)-shaped stats) and the stacked step ((P, S)-shaped stats) alike.
    """
    carry, _ = _run_cycles(step, skip_body, carry, 0, warmup, unroll)
    st_w, sched_w, dram_w = carry
    energy_on = all(k in dram_w for k in _ENERGY_SNAP)
    qos_on = all(k in dram_w for k in _QOS_SNAP)
    snap = {k: st_w[k] for k in _SNAP_KEYS}
    snap.update({k: dram_w[k] for k in _DRAM_SNAP})
    if energy_on:
        snap.update({k: dram_w[k] for k in _ENERGY_SNAP})
    if qos_on:
        snap.update({k: dram_w[k] for k in _QOS_SNAP})
    sched_snap = {k: sched_w[k] for k in _SCHED_SNAP if k in sched_w}
    carry, steps = _run_cycles(step, skip_body, carry, warmup,
                               warmup + n_cycles, unroll)
    st_f, sched_f, dram_f = carry

    cyc = jnp.float32(n_cycles)
    d = lambda k: (st_f[k] if k in st_f else dram_f[k]).astype(jnp.float32) \
        - snap[k].astype(jnp.float32)
    completed = d("completed")
    # ratios through `div_rn`, so the chip and the CPU report the same bits
    out = {
        "ipc": div_rn(d("insts_done"), cyc),
        "bw": div_rn(completed, cyc),                 # requests per cycle
        "mpkc": div_rn(d("emitted"), cyc) * 1000.0,
        "rbl": div_rn(d("hits"), jnp.maximum(d("issued"), 1.0)),
        "avg_lat": div_rn(d("sum_lat"), jnp.maximum(completed, 1.0)),
        "completed": completed,
        "emitted": d("emitted"),
        "outstanding_end": st_f["outstanding"].astype(jnp.float32),
        "inflight_unserved": (st_f["emitted"] - st_f["completed"]
                              ).astype(jnp.float32),
        "dl_met": d("dl_met"),
        "dl_missed": d("dl_missed"),
        "frames_released": d("frames_released"),
        # processed cycles in the measured window: == n_cycles when ticked,
        # fewer when the variable-step driver skips idle spans (the skip
        # ratio is 1 - sim_steps/n_cycles). A driver property, not a
        # simulation result — broadcast over any leading policy axis.
        "sim_steps": jnp.broadcast_to(
            steps, st_f["completed"].shape[:-1]).astype(jnp.float32),
    }
    if qos_on:
        out["lat_hist"] = d("lat_hist")               # (S, BINS) counts
    if "viol" in dram_f:
        # sanitizer counters are CUMULATIVE, not delta-measured: a warmup
        # violation is still a violation. (NV,) per sim — see
        # `validate.VIOLATIONS` for the layout, `validate.summarize` to name
        out["violations"] = dram_f["viol"].astype(jnp.float32)
    if "tl_ring" in dram_f:
        # flight-recorder ring is WINDOWED, not delta-measured: the last W
        # epochs of the whole run are the measurement. (W, K) per sim plus
        # the final epoch pointer that maps slots back to epochs — see
        # `telemetry.CHANNELS` / `metrics.timeline_breakdown`.
        out["telemetry"] = dram_f["tl_ring"].astype(jnp.float32)
        out["telemetry_epoch"] = dram_f["tl_epoch"].astype(jnp.float32)
    for k, name in _SCHED_SNAP.items():
        if k in sched_snap:
            out[name] = sched_f[k].astype(jnp.float32) \
                - sched_snap[k].astype(jnp.float32)
    if energy_on:
        # per-source dynamic energy stays (S,)-shaped for the CPU/GPU class
        # breakdown; per-channel background collapses to totals. Background
        # nJ derives from the integer cycle counters at metric time (the
        # counters, not a float accumulator, are what the skipping driver
        # can charge bit-identically in one add).
        out.update({
            "energy_act": d("e_act"),                 # (S,) ACT/PRE, nJ
            "energy_rw": d("e_rw"),                   # (S,) RD/WR bursts
            "energy_bg": jnp.sum(d("sb_cycles"), -1)
            * jnp.float32(cfg.energy_standby)
            + jnp.sum(d("pd_cycles"), -1) * jnp.float32(cfg.energy_pd),
            "energy_wake": jnp.sum(d("e_wake"), -1),
            "pd_cycles": jnp.sum(d("pd_cycles"), -1),
        })
    return out


def _one_sim(cfg: SimConfig, policy: str, n_cycles: int, warmup: int,
             unroll: int, skip: bool, pool: Dict[str, jax.Array],
             active: jax.Array, knobs=None) -> Dict[str, jax.Array]:
    cfg, pol, carry = _init(cfg, policy, knobs)
    step = policy_api.make_step(cfg, pol, pool, active)
    skip_body = policy_api.make_skip_step(cfg, pol, pool, active) \
        if skip else None
    return _scan_and_measure(cfg, step, skip_body, carry, n_cycles, warmup,
                             unroll)


# Per-cycle scan unroll factor. >1 trades trace size (compile time) for
# fewer loop iterations; 1 is best for the compile-dominated sweeps.
DEFAULT_UNROLL = 1
# Variable-step driver default. skip=True jumps idle spans (bit-identical
# to ticking — pinned by tests/test_event_skip.py) but pays a per-step
# witness cost, so it is OPT-IN: a win on bursty/idle-heavy streams (the
# `workloads.bursty_batch` family skips 60-97% of cycles), a pure loss on
# saturated parity sweeps (skip ratio ~0.05). The standard benchmark
# sweeps therefore tick; pass skip=True where traffic is idle-heavy.
DEFAULT_SKIP = False


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5),
                   donate_argnums=(6, 7))
def _sim_batch(cfg: SimConfig, policy: str, n_cycles: int, warmup: int,
               unroll: int, skip: bool, pool_batch, active_batch,
               knobs=None):
    """(W, ...) metrics; with `knobs` (a `Knobs` pytree stacked on a leading
    variant axis) the whole knob grid rides an inner vmap: (W, V, ...)."""
    if knobs is None:
        return jax.vmap(lambda p, a: _one_sim(cfg, policy, n_cycles, warmup,
                                              unroll, skip, p, a)
                        )(pool_batch, active_batch)
    return jax.vmap(lambda p, a: jax.vmap(
        lambda kn: _one_sim(cfg, policy, n_cycles, warmup, unroll, skip,
                            p, a, kn))(knobs))(pool_batch, active_batch)


def _check_pool(pool: Dict[str, Any], shape) -> None:
    """Host-side pool validation: malformed columns raise a named-column
    `ValueError` at dispatch instead of silently generating garbage traffic
    (negative periods wrap the frame arithmetic, out-of-range classes fall
    through every generator, shape mismatches broadcast into wrong-source
    traffic)."""
    shape = tuple(shape)
    float_cols = ("mpki", "inst_per_miss", "rbl")
    int_cols = ("blp", "dl_period", "dl_reqs", "dl_jitter", "src_class")
    for k, v in pool.items():
        v = np.asarray(v)
        if tuple(v.shape) != shape:
            raise ValueError(
                f"pool column {k!r}: shape {tuple(v.shape)} does not match "
                f"the active shape {shape}")
        if k in float_cols and v.dtype.kind not in "fiu":
            raise ValueError(
                f"pool column {k!r}: dtype {v.dtype} is not numeric")
        if k in int_cols and v.dtype.kind not in "iu":
            raise ValueError(
                f"pool column {k!r}: dtype {v.dtype} is not integral")
        if k == "is_gpu" and v.dtype.kind != "b":
            raise ValueError(
                f"pool column 'is_gpu': dtype {v.dtype} is not bool")
    for k in ("dl_period", "dl_reqs", "dl_jitter"):
        if k in pool and np.any(np.asarray(pool[k]) < 0):
            raise ValueError(
                f"pool column {k!r}: negative values (deadline streams "
                f"use 0 for 'no deadline', never negatives)")
    if "src_class" in pool:
        sc = np.asarray(pool["src_class"])
        if np.any((sc < 0) | (sc >= params.N_CLASSES)):
            raise ValueError(
                f"pool column 'src_class': values outside the CLASS_NAMES "
                f"range [0, {params.N_CLASSES}) "
                f"(known classes: {params.CLASS_NAMES})")


def prepare_pool(pool: Dict[str, Any], shape, copy: bool = False
                 ) -> Dict[str, Any]:
    """The one pool-preparation path shared by every driver.

    Validates the columns (named-column `ValueError` on malformed input),
    moves the pool to device (fresh buffers when `copy`, for donation
    safety) and completes the N-class schema: absent deadline-stream keys
    are zero-filled, and absent `src_class` is derived from the legacy
    `is_gpu`/`dl_period` partition — so 2-class pools run bit-identically
    through the N-class engine.
    """
    _check_pool(pool, shape)
    pool = {k: jnp.array(v, copy=True) if copy else jnp.asarray(v)
            for k, v in pool.items()}
    for k in ("dl_period", "dl_reqs", "dl_jitter"):
        if k not in pool:
            pool[k] = jnp.zeros(shape, jnp.int32)
    if "src_class" not in pool:
        pool["src_class"] = engine.derive_src_class(pool["is_gpu"],
                                                    pool["dl_period"])
    return pool


def simulate_async(cfg: SimConfig, policy: str,
                   pool_batch: Dict[str, np.ndarray],
                   active_batch: np.ndarray, n_cycles: int = 20_000,
                   warmup: int = 2_000, unroll: int = None,
                   skip: bool = None) -> Dict[str, jax.Array]:
    """Dispatch a batch sim and return DEVICE arrays without blocking.

    JAX's async dispatch means the scan executes in the background; callers
    (the benchmark sweeps) issue every policy's sim first and only then
    convert to numpy, overlapping device compute with host post-processing.
    Inputs are copied into fresh device buffers per call (`copy=True` — so
    the donation to the jitted computation can never invalidate a caller's
    live jax array).
    """
    pool_batch = prepare_pool(pool_batch, np.asarray(active_batch).shape,
                              copy=True)
    with warnings.catch_warnings():
        # donation is shape-matched: the f32 pool columns alias into the
        # f32 metric outputs, the small int/bool ones can't — fine
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return _sim_batch(cfg, policy, n_cycles, warmup,
                          DEFAULT_UNROLL if unroll is None else unroll,
                          DEFAULT_SKIP if skip is None else skip,
                          pool_batch, jnp.array(active_batch, copy=True))


def simulate(cfg: SimConfig, policy: str, pool_batch: Dict[str, np.ndarray],
             active_batch: np.ndarray, n_cycles: int = 20_000,
             warmup: int = 2_000, unroll: int = None,
             skip: bool = None) -> Dict[str, np.ndarray]:
    """pool_batch: dict of (W, S) arrays; active_batch: (W, S) bool."""
    out = simulate_async(cfg, policy, pool_batch, active_batch, n_cycles,
                         warmup, unroll, skip)
    return {k: np.asarray(v) for k, v in out.items()}


# ---------------------------------------------------------------------------
# knob-grid execution: a (variant, workload) sweep of ONE policy in ONE
# compiled program (ROADMAP "Tunable knobs contract"). Value-like knob
# points stack on a vmapped variant axis inside `_sim_batch`.
# ---------------------------------------------------------------------------

def _knob_points(cfg: SimConfig, points) -> params.Knobs:
    """Normalize a sequence of knob points (override dicts or `Knobs`) to a
    variant-stacked Knobs pytree."""
    kns = [params.Knobs.from_cfg(cfg, **pt) if isinstance(pt, dict) else pt
           for pt in points]
    return params.stack_knobs(kns)


def simulate_grid_async(cfg: SimConfig, policy: str, points,
                        pool_batch: Dict[str, np.ndarray],
                        active_batch: np.ndarray, n_cycles: int = 20_000,
                        warmup: int = 2_000, unroll: int = None,
                        skip: bool = None) -> Dict[str, jax.Array]:
    """One dispatch for a knob grid of one policy; (W, V, ...) device arrays.

    `points` is a sequence of value-knob override dicts (or `Knobs`); the
    grid shares a single scan body and jits into one XLA program, vmapped
    over (workload, variant). Period-like knobs are rejected here — they
    need per-slice traces (see `simulate_stacked_grid`).
    """
    pool_batch = prepare_pool(pool_batch, np.asarray(active_batch).shape,
                              copy=True)
    knobs = _knob_points(cfg, points)
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return _sim_batch(cfg, policy, n_cycles, warmup,
                          DEFAULT_UNROLL if unroll is None else unroll,
                          DEFAULT_SKIP if skip is None else skip,
                          pool_batch, jnp.array(active_batch, copy=True),
                          knobs)


def simulate_grid(cfg: SimConfig, policy: str, points,
                  pool_batch: Dict[str, np.ndarray],
                  active_batch: np.ndarray, n_cycles: int = 20_000,
                  warmup: int = 2_000, unroll: int = None,
                  skip: bool = None) -> list:
    """Per-variant (W, S) metric dicts, parallel to `points`.

    Each variant slice is bit-identical to a `simulate` run with the same
    values baked into SimConfig (pinned by tests/test_knobs.py)."""
    out = simulate_grid_async(cfg, policy, points, pool_batch, active_batch,
                              n_cycles, warmup, unroll, skip)
    host = {k: np.asarray(v) for k, v in out.items()}
    n = len(points)
    return [{k: v[:, i] for k, v in host.items()} for i in range(n)]


# ---------------------------------------------------------------------------
# stacked cross-policy execution: the whole stackable CentralizedPolicy
# family in ONE scan / ONE XLA program (see schedulers.make_stacked_step)
# ---------------------------------------------------------------------------

def stackable_names(cfg: SimConfig, policies=None) -> Tuple[str, ...]:
    """The subset of `policies` (default: full registry) that opts into the
    stacked execution path under this config."""
    names = policy_api.names() if policies is None else policies
    return tuple(n for n in names if policy_api.is_stackable(n, cfg))


def _init_stacked(cfg: SimConfig, policies: Tuple[str, ...]):
    """Resolve + validate the family and build the stacked (P, ...) carry."""
    from repro.core import schedulers

    pols = [policy_api.get(p) for p in policies]
    bad = [p for p in policies if not policy_api.is_stackable(p, cfg)]
    if bad:
        raise ValueError(f"not stackable under this config: {bad}")
    bufs = schedulers.stacked_union_state(cfg, pols)
    stack = schedulers._stack_trees
    P = len(pols)
    carry = (stack([engine.source_state(cfg)] * P), stack(bufs),
             stack([engine.dram_state(cfg)] * P))
    return pols, carry


def _one_sim_stacked(cfg: SimConfig, policies: Tuple[str, ...], n_cycles: int,
                     warmup: int, unroll: int, skip: bool,
                     pool: Dict[str, jax.Array], active: jax.Array
                     ) -> Dict[str, jax.Array]:
    from repro.core import schedulers

    pols, carry = _init_stacked(cfg, policies)
    step = schedulers.make_stacked_step(cfg, pols, pool, active)
    skip_body = schedulers.make_stacked_skip_step(cfg, pols, pool, active) \
        if skip else None
    return _scan_and_measure(cfg, step, skip_body, carry, n_cycles, warmup,
                             unroll)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5),
                   donate_argnums=(6, 7))
def _sim_batch_stacked(cfg: SimConfig, policies: Tuple[str, ...],
                       n_cycles: int, warmup: int, unroll: int, skip: bool,
                       pool_batch, active_batch):
    return jax.vmap(lambda p, a: _one_sim_stacked(cfg, policies, n_cycles,
                                                  warmup, unroll, skip, p, a)
                    )(pool_batch, active_batch)


def simulate_stacked_async(cfg: SimConfig, policies,
                           pool_batch: Dict[str, np.ndarray],
                           active_batch: np.ndarray, n_cycles: int = 20_000,
                           warmup: int = 2_000, unroll: int = None,
                           skip: bool = None) -> Dict[str, jax.Array]:
    """One dispatch for the whole stacked family; (W, P, S) device arrays.

    The per-policy trace+compile is amortized: the family shares a single
    scan body and jits into one XLA program, vmapped over (policy, workload).
    Same async-dispatch / buffer-copy contract as `simulate_async`.
    """
    pool_batch = prepare_pool(pool_batch, np.asarray(active_batch).shape,
                              copy=True)
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return _sim_batch_stacked(cfg, tuple(policies), n_cycles, warmup,
                                  DEFAULT_UNROLL if unroll is None else unroll,
                                  DEFAULT_SKIP if skip is None else skip,
                                  pool_batch, jnp.array(active_batch,
                                                        copy=True))


def simulate_stacked(cfg: SimConfig, policies,
                     pool_batch: Dict[str, np.ndarray],
                     active_batch: np.ndarray, n_cycles: int = 20_000,
                     warmup: int = 2_000, unroll: int = None,
                     skip: bool = None) -> Dict[str, Dict[str, np.ndarray]]:
    """Per-policy (W, S) metrics for a stacked family, keyed by name.

    Results are bit-identical to per-policy `simulate` calls (pinned by
    tests/test_stacked_vmap.py against the golden digests); `sim_steps` is
    the exception — the stacked slices share one variable-step loop, so
    they report the family's common step count, not the per-policy one.
    """
    out = simulate_stacked_async(cfg, policies, pool_batch, active_batch,
                                 n_cycles, warmup, unroll, skip)
    host = {k: np.asarray(v) for k, v in out.items()}
    return {pol: {k: v[:, i] for k, v in host.items()}
            for i, pol in enumerate(policies)}


# ---------------------------------------------------------------------------
# stacked (policy x knob-variant) grid: the DSE driver. Slices stack policy
# AND knob variants on the same leading axis; value-like knobs ride the
# stacked Knobs pytree through the shared engine work while period-like
# overrides re-trace only that slice's hooks (trace-time dispatch, so every
# boundary cond and skip witness survives).
# ---------------------------------------------------------------------------

def _norm_grid_slices(cfg: SimConfig, slices):
    """Split mixed per-slice overrides into the static (hashable) slice
    spec — (policy, sorted period-knob items) — and the value-knob points.
    """
    static, points = [], []
    for s in slices:
        name, ov = (s, {}) if isinstance(s, str) else s
        per, val = params.split_overrides(dict(ov))
        static.append((name, tuple(sorted(per.items()))))
        points.append(params.Knobs.from_cfg(cfg, **val))
    return tuple(static), points


def _init_stacked_grid(cfg: SimConfig, slices):
    """Resolve + validate grid slices; (pols, per-slice cfgs, carry)."""
    from repro.core import schedulers

    pols = [policy_api.get(name) for name, _ in slices]
    cfgs = [cfg.replace(**dict(ov)) for _, ov in slices]
    bad = [name for (name, _), c in zip(slices, cfgs)
           if not policy_api.is_stackable(name, c)]
    if bad:
        raise ValueError(f"not stackable under this config: {bad}")
    # period overrides never touch array shapes, so the union schema and the
    # engine state stack exactly as in `_init_stacked`
    bufs = schedulers.stacked_union_state(cfg, pols)
    stack = schedulers._stack_trees
    P = len(pols)
    carry = (stack([engine.source_state(cfg)] * P), stack(bufs),
             stack([engine.dram_state(cfg)] * P))
    return pols, cfgs, carry


def _one_sim_stacked_grid(cfg: SimConfig, slices, n_cycles: int, warmup: int,
                          unroll: int, skip: bool, pool, active, knobs):
    from repro.core import schedulers

    pols, cfgs, carry = _init_stacked_grid(cfg, slices)
    bcfgs = [params.bind(c, policy_api.resolve_knobs(
        c, p, schedulers._slice_tree(knobs, i)))
        for i, (p, c) in enumerate(zip(pols, cfgs))]
    step = schedulers.make_stacked_step(cfg, pols, pool, active,
                                        cfgs=bcfgs, knobs=knobs)
    skip_body = schedulers.make_stacked_skip_step(
        cfg, pols, pool, active, cfgs=bcfgs, knobs=knobs) if skip else None
    return _scan_and_measure(cfg, step, skip_body, carry, n_cycles, warmup,
                             unroll)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5),
                   donate_argnums=(6, 7))
def _sim_batch_stacked_grid(cfg: SimConfig, slices, n_cycles: int,
                            warmup: int, unroll: int, skip: bool,
                            pool_batch, active_batch, knobs):
    return jax.vmap(lambda p, a: _one_sim_stacked_grid(
        cfg, slices, n_cycles, warmup, unroll, skip, p, a, knobs)
        )(pool_batch, active_batch)


def simulate_stacked_grid_async(cfg: SimConfig, slices,
                                pool_batch: Dict[str, np.ndarray],
                                active_batch: np.ndarray,
                                n_cycles: int = 20_000, warmup: int = 2_000,
                                unroll: int = None, skip: bool = None
                                ) -> Dict[str, jax.Array]:
    """One dispatch for a (policy x knob-variant) grid; (W, N, S) arrays.

    `slices` is a sequence of policy names or (policy, overrides) pairs;
    overrides may mix value-like knobs (batched on the variant axis) and
    period-like knobs (per-slice trace-time dispatch). Policies may repeat
    — e.g. 6 policies x 4 knob points = 24 slices in ONE XLA program.
    """
    static, points = _norm_grid_slices(cfg, slices)
    knobs = params.stack_knobs(points)
    pool_batch = prepare_pool(pool_batch, np.asarray(active_batch).shape,
                              copy=True)
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return _sim_batch_stacked_grid(
            cfg, static, n_cycles, warmup,
            DEFAULT_UNROLL if unroll is None else unroll,
            DEFAULT_SKIP if skip is None else skip,
            pool_batch, jnp.array(active_batch, copy=True), knobs)


def simulate_stacked_grid(cfg: SimConfig, slices,
                          pool_batch: Dict[str, np.ndarray],
                          active_batch: np.ndarray, n_cycles: int = 20_000,
                          warmup: int = 2_000, unroll: int = None,
                          skip: bool = None) -> list:
    """Per-slice (W, S) metric dicts, parallel to `slices`.

    Each slice is bit-identical to a solo `simulate` run with the same
    overrides baked into SimConfig (tests/test_knobs.py), with the usual
    stacked-path exception for the shared `sim_steps` step meter."""
    out = simulate_stacked_grid_async(cfg, slices, pool_batch, active_batch,
                                      n_cycles, warmup, unroll, skip)
    host = {k: np.asarray(v) for k, v in out.items()}
    return [{k: v[:, i] for k, v in host.items()}
            for i in range(len(slices))]


def simulate_debug_stacked(cfg: SimConfig, policies,
                           pool: Dict[str, np.ndarray], active: np.ndarray,
                           n_cycles: int = 2_000, skip: bool = None):
    """Stacked-path analog of `simulate_debug` (no workload vmap).

    Returns {policy: (src_state, sched_state, dram_state)} numpy trees —
    each policy's slice of the final stacked raw state, with the scheduler
    state restricted to that policy's own (unpadded) keys.
    """
    from repro.core import schedulers

    policies = tuple(policies)
    pool = prepare_pool(pool, (cfg.n_src,))
    pols, carry = _init_stacked(cfg, policies)
    active = jnp.asarray(active)
    step = schedulers.make_stacked_step(cfg, pols, pool, active)
    skip_body = schedulers.make_stacked_skip_step(cfg, pols, pool, active) \
        if (DEFAULT_SKIP if skip is None else skip) else None

    @jax.jit
    def run(carry):
        return _run_cycles(step, skip_body, carry, 0, n_cycles,
                           DEFAULT_UNROLL)[0]

    st_f, sched_f, dram_f = run(carry)
    own = [set(p.init_state(cfg)) for p in pols]
    take = lambda tree, i, keys=None: {
        k: np.asarray(v[i]) for k, v in tree.items()
        if keys is None or k in keys}
    return {pol: (take(st_f, i), take(sched_f, i, own[i]), take(dram_f, i))
            for i, pol in enumerate(policies)}


def simulate_debug(cfg: SimConfig, policy: str, pool: Dict[str, np.ndarray],
                   active: np.ndarray, n_cycles: int = 2_000,
                   skip: bool = None):
    """Single-workload run returning the FINAL RAW STATE (invariant tests).

    pool: dict of (S,) arrays; active: (S,) bool.
    Returns (src_state, sched_state, dram_state) as numpy trees.
    """
    pool = prepare_pool(pool, (cfg.n_src,))
    cfg, pol, carry = _init(cfg, policy)
    active = jnp.asarray(active)
    step = policy_api.make_step(cfg, pol, pool, active)
    skip_body = policy_api.make_skip_step(cfg, pol, pool, active) \
        if (DEFAULT_SKIP if skip is None else skip) else None

    @jax.jit
    def run(carry):
        return _run_cycles(step, skip_body, carry, 0, n_cycles,
                           DEFAULT_UNROLL)[0]

    st_f, sched_f, dram_f = run(carry)
    to_np = lambda t: jax.tree_util.tree_map(np.asarray, t)
    return to_np(st_f), to_np(sched_f), to_np(dram_f)


def perf_vector(cfg: SimConfig, metrics: Dict[str, np.ndarray],
                pool_batch: Dict[str, np.ndarray]) -> np.ndarray:
    """Per-source performance, (W, S): IPC for CPU-class sources, attained
    BW for the streaming classes (GPU, HWA)."""
    if "src_class" in pool_batch:
        cls = np.asarray(pool_batch["src_class"])
    else:
        dlp = np.asarray(pool_batch.get(
            "dl_period", np.zeros_like(pool_batch["is_gpu"], np.int32)))
        cls = np.asarray(engine.derive_src_class(
            np.asarray(pool_batch["is_gpu"], bool), dlp))
    return np.where(cls == params.CLS_CPU, metrics["ipc"], metrics["bw"])
