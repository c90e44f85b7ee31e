"""Shared simulator machinery: source/core models, DRAM state, completion.

Everything is expressed as fixed-shape masked array ops so the per-cycle step
jits into one `lax.scan` body and `vmap`s over workloads.

Shapes (per workload): S = n_src sources, C = channels, B = banks/channel.
Completion ring: RING > max access latency, indexed by absolute cycle % RING.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy, qos, telemetry, validate
from repro.core.params import (CLS_CPU, CLS_GPU, CLS_HWA, SimConfig,
                               SourcePool)

RING = 64
NEG_T = -100_000
# "no event" sentinel for the variable-step driver's next-event witnesses:
# far beyond any simulated horizon, small enough that int32 arithmetic on
# witness candidates can never wrap
INF_T = 1 << 30

# source_state keys added by the N-class requester model (golden digests
# predate them; the digest tests whitelist exactly this tuple)
NCLASS_SRC_KEYS = ("frames_released",)


@functools.lru_cache(maxsize=None)
def addr_base(n_src: int, n_channels: int, n_banks: int) -> np.ndarray:
    """Loop-invariant address-gen stripe origins, hoisted out of the
    per-cycle step (embedded as a literal constant in the trace)."""
    return (np.arange(n_src, dtype=np.int32) * 3) % (n_channels * n_banks)


# ---------------------------------------------------------------------------
# one-hot masked writes and small-table reads — the hot-loop replacement
# for scatter and gather ops. XLA:CPU lowers gather/scatter inside a scan
# body to serial per-element loops, and the TPU runs a batched gather
# element by element too; a compare-mask + select over the same (C, N)
# array fuses into the surrounding elementwise work and is ~10x faster. All
# per-cycle state updates with traced indices go through these.
# ---------------------------------------------------------------------------

def masked_set(a: jax.Array, idx: jax.Array, v, do: jax.Array) -> jax.Array:
    """a[c, idx[c]] = v[c] where do[c]; a: (C, N), idx/do: (C,)."""
    mask = (jnp.arange(a.shape[-1]) == idx[:, None]) & do[:, None]
    if jnp.ndim(v) == 1:
        v = v[:, None]
    return jnp.where(mask, v, a)


def masked_set2(a: jax.Array, idx1: jax.Array, idx2: jax.Array, v,
                do: jax.Array) -> jax.Array:
    """a[c, idx1[c], idx2[c]] = v[c] where do[c]; a: (C, M, N)."""
    mask = (jnp.arange(a.shape[-2])[:, None] == idx1[:, None, None]) & \
        (jnp.arange(a.shape[-1]) == idx2[:, None, None]) & \
        do[:, None, None]
    if jnp.ndim(v) == 1:
        v = v[:, None, None]
    return jnp.where(mask, v, a)


def masked_add(a: jax.Array, idx: jax.Array, v, do: jax.Array) -> jax.Array:
    """a[c, idx[c]] += v[c] where do[c]; a: (C, N), idx/do: (C,)."""
    mask = (jnp.arange(a.shape[-1]) == idx[:, None]) & do[:, None]
    if jnp.ndim(v) == 1:
        v = v[:, None]
    return a + mask.astype(a.dtype) * v


def accum_by_index(acc: jax.Array, idx: jax.Array, v, do: jax.Array
                   ) -> jax.Array:
    """acc[idx[c]] += v[c] where do[c]; acc: (N,), idx/do: (C,).

    Duplicate indices across channels accumulate, matching scatter-add.
    """
    onehot = (jnp.arange(acc.shape[0]) == idx[:, None]) & do[:, None]
    if jnp.ndim(v) == 1:
        v = v[:, None]
    return acc + jnp.sum(onehot.astype(acc.dtype) * v, axis=0)


def small_lookup(table: jax.Array, idx: jax.Array) -> jax.Array:
    """out[..., n] = table[..., idx[..., n]] for a small last axis of table.

    table: (..., K), its leading axes broadcastable against idx's leading
    axes; idx: (..., N) int, every index in [0, K) (not clamped: an index
    out of range reads table[..., 0]). A Python-unrolled chain of K - 1
    selects with no arithmetic, so it is exact for every dtype (NaN
    payloads and -0.0 included) and keeps the dtype, with N on the minor
    axis.
    """
    out = jnp.broadcast_to(table[..., :1], idx.shape)
    for k in range(1, table.shape[-1]):
        out = jnp.where(idx == k, table[..., k:k + 1], out)
    return out


# ---------------------------------------------------------------------------
# cheap counter RNG (threefry is too heavy inside a per-cycle scan)
# ---------------------------------------------------------------------------

def lcg_step(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: uint32 state. Returns (new_state, u01 float32)."""
    x = x * jnp.uint32(1664525) + jnp.uint32(1013904223)
    u = (x >> jnp.uint32(8)).astype(jnp.float32) / jnp.float32(1 << 24)
    return x, u


def lcg_skip(x: jax.Array, k: jax.Array) -> jax.Array:
    """Advance the LCG state by a traced number of steps in O(log k).

    The per-step map f(x) = A·x + C is affine, so f^k is the affine map
    obtained by binary exponentiation over k's bits — the closed form the
    variable-step driver uses to keep skipped spans bit-identical to
    ticking (each skipped cycle consumes its rng draws without observing
    them). k: scalar int (>= 0; k = 0 is the identity). uint32 wrap-around
    arithmetic throughout, exactly matching repeated `lcg_step`.
    """
    A, C = jnp.uint32(1664525), jnp.uint32(1013904223)
    kk = k.astype(jnp.uint32)
    acc_a, acc_c = jnp.uint32(1), jnp.uint32(0)
    pow_a, pow_c = A, C
    for i in range(32):                     # static: k fits in 32 bits
        take = ((kk >> jnp.uint32(i)) & jnp.uint32(1)) == jnp.uint32(1)
        acc_a, acc_c = (jnp.where(take, pow_a * acc_a, acc_a),
                        jnp.where(take, pow_a * acc_c + pow_c, acc_c))
        pow_a, pow_c = pow_a * pow_a, pow_a * pow_c + pow_c
    return acc_a * x + acc_c


# ---------------------------------------------------------------------------
# state construction
# ---------------------------------------------------------------------------

def source_state(cfg: SimConfig) -> Dict[str, Any]:
    S = cfg.n_src
    z_i = jnp.zeros((S,), jnp.int32)
    z_f = jnp.zeros((S,), jnp.float32)
    return {
        "insts_acc": z_f, "insts_done": z_f,
        "outstanding": z_i, "emitted": z_i, "completed": z_i,
        "sum_lat": z_f,
        "pend_valid": jnp.zeros((S,), bool),
        "pend_bank": z_i, "pend_row": z_i, "pend_birth": z_i,
        "cur_bank": z_i, "cur_row": z_i, "bank_ptr": z_i,
        "rng": (jnp.arange(S, dtype=jnp.uint32) * jnp.uint32(2654435761)
                + jnp.uint32(12345)),
        # measurement helpers (Fig 1): bank occupancy snapshots
        "blp_sum": z_f, "blp_n": z_f,
        # frame-deadline accounting (HWA class / SMS-DASH)
        "period_done": z_i, "dl_met": z_i, "dl_missed": z_i,
        "frames_released": z_i,
    }


def dram_state(cfg: SimConfig) -> Dict[str, Any]:
    C, B = cfg.n_channels, cfg.n_banks
    return {
        "bank_free": jnp.zeros((C, B), jnp.int32),
        "open_row": jnp.full((C, B), -1, jnp.int32),
        "open_valid": jnp.zeros((C, B), bool),
        "act_ring": jnp.full((C, 4), NEG_T, jnp.int32),
        "bus_free": jnp.zeros((C,), jnp.int32),
        "ring": jnp.zeros((RING, cfg.n_src), jnp.int32),
        # measured service stats
        "hits": jnp.zeros((cfg.n_src,), jnp.int32),
        "issued": jnp.zeros((cfg.n_src,), jnp.int32),
        # energy counters (empty dict when cfg.energy_enabled is off)
        **energy.energy_state(cfg),
        # QoS latency histogram (empty dict when cfg.qos_enabled is off)
        **qos.qos_state(cfg),
        # invariant-sanitizer counters (empty when cfg.validate_enabled off)
        **validate.validate_state(cfg),
        # flight-recorder ring (empty when cfg.telemetry_enabled off)
        **telemetry.telemetry_state(cfg),
    }


def derive_src_class(is_gpu: jax.Array, dl_period: jax.Array) -> jax.Array:
    """Class ids for legacy pools that predate `src_class`: the GPU flag
    wins, a deadline stream marks an HWA, everything else is a CPU core.
    This reproduces the old `is_gpu` / `dl_period > 0` partition exactly,
    so derived classes keep 2-class pools bit-identical."""
    return jnp.where(jnp.asarray(is_gpu, bool), CLS_GPU,
                     jnp.where(jnp.asarray(dl_period) > 0, CLS_HWA,
                               CLS_CPU)).astype(jnp.int32)


def pool_arrays(pool: SourcePool) -> Dict[str, jax.Array]:
    S = len(pool.mpki)
    dlp = pool.dl_period if pool.dl_period is not None else np.zeros(S)
    dlr = pool.dl_reqs if pool.dl_reqs is not None else np.zeros(S)
    dlj = pool.dl_jitter if pool.dl_jitter is not None else np.zeros(S)
    out = {
        "mpki": jnp.asarray(pool.mpki, jnp.float32),
        "inst_per_miss": jnp.asarray(pool.inst_per_miss(), jnp.float32),
        "rbl": jnp.asarray(pool.rbl, jnp.float32),
        "blp": jnp.asarray(pool.blp, jnp.int32),
        "is_gpu": jnp.asarray(pool.is_gpu, bool),
        "dl_period": jnp.asarray(dlp, jnp.int32),
        "dl_reqs": jnp.asarray(dlr, jnp.int32),
        "dl_jitter": jnp.asarray(dlj, jnp.int32),
    }
    out["src_class"] = (jnp.asarray(pool.src_class, jnp.int32)
                        if pool.src_class is not None else
                        derive_src_class(out["is_gpu"], out["dl_period"]))
    return out


# ---------------------------------------------------------------------------
# per-cycle: core progress + request generation into the pending register
# ---------------------------------------------------------------------------

def frame_release_offset(S: int, frame: jax.Array, dl_jitter: jax.Array
                         ) -> jax.Array:
    """Per-(source, frame) release jitter in [0, dl_jitter] cycles.

    Stateless integer hash of the source id and frame index (LCG-style
    mixing), NOT a draw from the source `rng` stream — consuming that
    stream would shift every downstream address draw and break the
    2-class bit-identity contract. Zero jitter hashes to offset 0.
    """
    mix = (jnp.arange(S, dtype=jnp.uint32) * jnp.uint32(2654435761)) ^ \
        (frame.astype(jnp.uint32) * jnp.uint32(2246822519))
    h = mix * jnp.uint32(1664525) + jnp.uint32(1013904223)
    span = jnp.asarray(dl_jitter).astype(jnp.uint32) + jnp.uint32(1)
    return ((h >> jnp.uint32(8)) % span).astype(jnp.int32)


def source_tick(cfg: SimConfig, pool: Dict[str, jax.Array],
                st: Dict[str, Any], active: jax.Array, t: jax.Array
                ) -> Dict[str, Any]:
    """Advance cores one cycle; fill empty pending registers.

    active: (S,) bool — which sources exist in this workload (masking lets a
    single jitted sim serve every workload mix and the alone-runs).

    The traffic generator is picked by `pool["src_class"]`: CPU cores are
    MLP-limit cores (instruction progress between misses), the GPU is an
    always-wanting streaming generator, HWAs emit periodic frame bursts —
    each frame releases up to `dl_reqs` requests after a per-frame jitter
    offset, due at the next `dl_period` boundary (`deadline_tick`).
    """
    S = cfg.n_src
    cls = pool["src_class"]
    is_gpu = cls == CLS_GPU
    is_hwa = cls == CLS_HWA
    is_cpu = cls == CLS_CPU
    # GPU/HWA are DMA-like streaming engines: deep request queues
    mshr = jnp.where(is_gpu, cfg.gpu_mshr,
                     jnp.where(is_hwa, cfg.hwa_mshr, cfg.cpu_mshr))
    room = st["outstanding"] < mshr
    # CPU: progress instructions while not blocked on a full window and not
    # waiting for MC admission
    can_run = active & is_cpu & room & ~st["pend_valid"]
    st = dict(st)
    st["insts_acc"] = st["insts_acc"] + jnp.where(can_run, cfg.cpu_ipc, 0.0)
    st["insts_done"] = st["insts_done"] + jnp.where(can_run, cfg.cpu_ipc, 0.0)

    want_cpu = active & is_cpu & (st["insts_acc"] >= pool["inst_per_miss"]) \
        & ~st["pend_valid"] & room
    want_gpu = active & is_gpu & ~st["pend_valid"] & room
    # HWA: emit only this frame's remaining demand, once the frame's
    # jittered release point has passed (offset 0 when dl_jitter is 0,
    # which keeps legacy deadline sources bit-identical)
    period = jnp.maximum(pool["dl_period"], 1)
    released = jnp.mod(t, period) >= \
        frame_release_offset(S, t // period, pool["dl_jitter"])
    want_accel = active & is_hwa & ~st["pend_valid"] & room & released & \
        (st["period_done"] + st["outstanding"] < pool["dl_reqs"])
    want = want_cpu | want_gpu | want_accel

    # address generation (one LCG draw per source per cycle; cheap)
    rng, u = lcg_step(st["rng"])
    rng2, u2 = lcg_step(rng)
    st["rng"] = rng2
    same = u < pool["rbl"]
    n_banks_total = cfg.n_channels * cfg.n_banks
    base = jnp.asarray(addr_base(S, cfg.n_channels, cfg.n_banks))
    new_ptr = st["bank_ptr"] + 1
    new_bank = (base + new_ptr % jnp.maximum(pool["blp"], 1)) % n_banks_total
    new_row = (u2 * cfg.n_rows).astype(jnp.int32)
    bank = jnp.where(same, st["cur_bank"], new_bank)
    row = jnp.where(same, st["cur_row"], new_row)

    st["cur_bank"] = jnp.where(want, bank, st["cur_bank"])
    st["cur_row"] = jnp.where(want, row, st["cur_row"])
    st["bank_ptr"] = jnp.where(want & ~same, new_ptr, st["bank_ptr"])
    st["pend_bank"] = jnp.where(want, bank, st["pend_bank"])
    st["pend_row"] = jnp.where(want, row, st["pend_row"])
    st["pend_birth"] = jnp.where(want, t, st["pend_birth"])
    st["pend_valid"] = st["pend_valid"] | want
    st["insts_acc"] = jnp.where(want_cpu, st["insts_acc"] -
                                pool["inst_per_miss"], st["insts_acc"])
    st["emitted"] = st["emitted"] + want.astype(jnp.int32)
    st["outstanding"] = st["outstanding"] + want.astype(jnp.int32)
    return st


def completions_tick(st: Dict[str, Any], dram: Dict[str, Any], t: jax.Array
                     ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Return requests whose data completed this cycle to their sources."""
    slot = jnp.mod(t, RING)
    done = dram["ring"][slot]                       # (S,)
    st = dict(st)
    dram = dict(dram)
    st["outstanding"] = st["outstanding"] - done
    st["completed"] = st["completed"] + done
    st["period_done"] = st["period_done"] + done
    dram["ring"] = dram["ring"].at[slot].set(0)     # scalar-index slice
    return st, dram


def deadline_tick(cfg: SimConfig, pool: Dict[str, jax.Array],
                  st: Dict[str, Any], t: jax.Array) -> Dict[str, Any]:
    """Frame-boundary accounting for deadline (HWA/DASH) sources.

    Every elapsed frame is settled at its boundary as met or missed, so
    `frames_released == dl_met + dl_missed` holds at any boundary-aligned
    observation point (pinned by tests/test_nclass.py).
    """
    has_dl = pool["dl_period"] > 0
    boundary = has_dl & (t > 0) & \
        (jnp.mod(t, jnp.maximum(pool["dl_period"], 1)) == 0)
    met = boundary & (st["period_done"] >= pool["dl_reqs"])
    st = dict(st)
    st["frames_released"] = st["frames_released"] + boundary.astype(jnp.int32)
    st["dl_met"] = st["dl_met"] + met.astype(jnp.int32)
    st["dl_missed"] = st["dl_missed"] + (boundary & ~met).astype(jnp.int32)
    st["period_done"] = jnp.where(boundary, 0, st["period_done"])
    return st


# ---------------------------------------------------------------------------
# variable-step driver witnesses (ROADMAP "Variable-step driver contract").
#
# Each witness returns the earliest cycle > t at which the corresponding
# per-cycle hook could do anything beyond the closed-form accruals that
# `skip_sources`/`energy.skip_accrue` replay. Witnesses are evaluated on
# POST-cycle-t state and may be conservative-early (returning a cycle at
# which nothing happens is always safe — processing it is ticked-identical);
# they must never be late. INF_T means "no event from this component".
# ---------------------------------------------------------------------------

def next_source_event(cfg: SimConfig, pool: Dict[str, jax.Array],
                      st: Dict[str, Any], active: jax.Array, t: jax.Array
                      ) -> jax.Array:
    """Earliest cycle > t at which `source_tick` could emit a request or
    `deadline_tick` could settle a frame boundary, assuming no completion
    or issue lands first (those are covered by separate witnesses — any of
    them firing ends the span before this witness is trusted past it)."""
    S = cfg.n_src
    cls = pool["src_class"]
    is_gpu = cls == CLS_GPU
    is_hwa = cls == CLS_HWA
    is_cpu = cls == CLS_CPU
    mshr = jnp.where(is_gpu, cfg.gpu_mshr,
                     jnp.where(is_hwa, cfg.hwa_mshr, cfg.cpu_mshr))
    free = active & ~st["pend_valid"] & (st["outstanding"] < mshr)
    INF = jnp.int32(INF_T)
    t1 = t + 1
    # GPU: wants every cycle while its pending register is free
    w_gpu = jnp.where(jnp.any(free & is_gpu), t1, INF)
    # CPU: next inter-miss crossing. `source_tick` adds ipc then compares,
    # so the crossing cycle is t + ceil((ipm - acc)/ipc); floor(..) is the
    # conservative-early form (never late: floor <= ceil, and f32 rounding
    # on these integer-grid values is well under one whole step). Batch
    # accrual of k*ipc is bit-exact only for power-of-two ipc, so any other
    # ipc pins the witness at t+1 (trace-time check — ipc is static).
    can_run = free & is_cpu
    ipc = float(cfg.cpu_ipc)
    if ipc > 0.0 and math.log2(ipc).is_integer():
        kf = (pool["inst_per_miss"] - st["insts_acc"]) / jnp.float32(ipc)
        k = jnp.maximum(jnp.floor(kf).astype(jnp.int32), 1)
        w_cpu = jnp.min(jnp.where(can_run, t + k, INF))
    else:
        w_cpu = jnp.where(jnp.any(can_run), t1, INF)
    # HWA: the current frame's jittered release point (clamped below by t+1
    # — if already released and still wanting, the event is immediate)
    period = jnp.maximum(pool["dl_period"], 1)
    frame = t1 // period
    rel = frame * period + frame_release_offset(S, frame, pool["dl_jitter"])
    demand = st["period_done"] + st["outstanding"] < pool["dl_reqs"]
    hwa_ok = free & is_hwa & demand & (pool["dl_period"] > 0)
    w_hwa = jnp.min(jnp.where(hwa_ok, jnp.maximum(rel, t1), INF))
    # frame boundary: `deadline_tick` settles every deadline source in the
    # pool at its boundary regardless of `active` (it has no active mask)
    has_dl = pool["dl_period"] > 0
    w_bnd = jnp.min(jnp.where(has_dl, (t // period + 1) * period, INF))
    return jnp.minimum(jnp.minimum(w_gpu, w_cpu), jnp.minimum(w_hwa, w_bnd))


def next_completion(dram: Dict[str, Any], t: jax.Array) -> jax.Array:
    """Earliest cycle > t whose completion-ring slot holds any request.

    Every in-flight request lands within RING cycles of issue, so the ring
    fully describes pending completions."""
    pend = jnp.any(dram["ring"] > 0, axis=1)                 # (RING,)
    slots = jnp.arange(RING, dtype=jnp.int32)
    dt = jnp.mod(slots - (t + 1), RING)                      # 0..RING-1
    return jnp.min(jnp.where(pend, t + 1 + dt, jnp.int32(INF_T)))


def skip_sources(cfg: SimConfig, pool: Dict[str, jax.Array],
                 st: Dict[str, Any], active: jax.Array, k: jax.Array
                 ) -> Dict[str, Any]:
    """Replay k skipped (event-free) cycles of `source_tick` in closed form:
    the two unconditional rng draws per cycle and the CPU instruction
    accrual. Everything else is frozen by the witness contract (no source
    wants, no completions, no boundaries inside the span)."""
    st = dict(st)
    st["rng"] = lcg_skip(st["rng"], 2 * k)
    cls = pool["src_class"]
    mshr = jnp.where(cls == CLS_GPU, cfg.gpu_mshr,
                     jnp.where(cls == CLS_HWA, cfg.hwa_mshr, cfg.cpu_mshr))
    can_run = active & (cls == CLS_CPU) & (st["outstanding"] < mshr) \
        & ~st["pend_valid"]
    add = jnp.where(can_run, k.astype(jnp.float32) * jnp.float32(cfg.cpu_ipc),
                    jnp.float32(0.0))
    st["insts_acc"] = st["insts_acc"] + add
    st["insts_done"] = st["insts_done"] + add
    return st


# ---------------------------------------------------------------------------
# DRAM eligibility + issue
# ---------------------------------------------------------------------------

def eligibility(cfg: SimConfig, dram: Dict[str, Any], bank: jax.Array,
                row: jax.Array, valid: jax.Array, t: jax.Array):
    """Per-candidate issue legality on every channel at once.

    bank/row/valid: (C, N) candidate arrays, row c on channel c (bank is
    bank-in-channel index). Returns (eligible, lat, is_hit), each (C, N).
    """
    tm = cfg.timing
    openv = small_lookup(dram["open_valid"], bank)
    openr = small_lookup(dram["open_row"], bank)
    is_hit = openv & (openr == row)
    lat = jnp.where(is_hit, tm.lat_hit,
                    jnp.where(openv, tm.lat_conflict, tm.lat_closed)
                    ).astype(jnp.int32)
    ok_bank = small_lookup(dram["bank_free"] <= t, bank)
    oldest_act = jnp.min(dram["act_ring"], axis=1, keepdims=True)
    ok_faw = is_hit | (t - oldest_act >= tm.t_faw)
    ok_bus = t + lat >= dram["bus_free"][:, None]
    return valid & ok_bank & ok_faw & ok_bus, lat, is_hit


def issue_channels(cfg: SimConfig, dram: Dict[str, Any], st: Dict[str, Any],
                   do_issue: jax.Array, bank: jax.Array, row: jax.Array,
                   src: jax.Array, birth: jax.Array, lat: jax.Array,
                   is_hit: jax.Array, t: jax.Array):
    """Commit at most one issue per channel (all args (C,) vectors).

    Per-channel DRAM rows are disjoint; the per-source scatters (ring, hits,
    issued, sum_lat) use `.add`, which is exact for the integer-valued f32
    accumulators involved, so channels commute.
    """
    tm = cfg.timing
    dram = dict(dram)
    st = dict(st)
    if cfg.validate_enabled:
        # timing compliance is checked against the PRE-update DRAM state
        dram["viol"] = dram["viol"] + validate.issue_counts(
            cfg, dram, do_issue, bank, lat, is_hit, t)
    done = t + lat + tm.t_burst                                 # (C,)
    dram["bank_free"] = masked_set(dram["bank_free"], bank, done, do_issue)
    dram["open_row"] = masked_set(dram["open_row"], bank, row, do_issue)
    dram["open_valid"] = masked_set(dram["open_valid"], bank, True, do_issue)
    # activate bookkeeping (tFAW): replace the oldest entry per channel
    do_act = do_issue & ~is_hit
    amin = jnp.argmin(dram["act_ring"], axis=1)                 # (C,)
    dram["act_ring"] = masked_set(dram["act_ring"], amin, t, do_act)
    dram["bus_free"] = jnp.where(do_issue, done, dram["bus_free"])
    # completion ring: a (RING, S) one-hot mask is heavier than this tiny
    # 1-element-per-channel scatter-add, so the scatter stays
    slot = jnp.mod(done, RING)
    safe_src = jnp.where(do_issue, src, 0)
    dram["ring"] = dram["ring"].at[slot, safe_src].add(
        jnp.where(do_issue, 1, 0))
    dram["hits"] = accum_by_index(dram["hits"], src, 1,
                                  do_issue & is_hit)
    dram["issued"] = accum_by_index(dram["issued"], src, 1, do_issue)
    st["sum_lat"] = accum_by_index(
        st["sum_lat"], src, (done - birth).astype(jnp.float32), do_issue)
    dram = energy.on_issue(cfg, dram, do_issue, src, is_hit, done)
    if cfg.qos_enabled:
        dram["lat_hist"] = qos.on_issue(cfg, dram["lat_hist"], src,
                                        done - birth, do_issue)
    return dram, st


def channel_of(cfg: SimConfig, bank_global: jax.Array) -> jax.Array:
    return jnp.mod(bank_global, cfg.n_channels)


def bank_in_channel(cfg: SimConfig, bank_global: jax.Array) -> jax.Array:
    return bank_global // cfg.n_channels
