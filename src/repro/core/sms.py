"""Staged Memory Scheduler — the paper's contribution (§2).

Three decoupled stages, all simple FIFOs:
  1. per-source batch formation FIFOs (C, S, F): consecutive same-(bank,row)
     requests form a batch; ready on row-change / age threshold / full FIFO;
  2. batch scheduler: picks a ready batch — SJF (fewest in-flight across all
     stages) with probability p, round-robin with 1-p — then drains it one
     request/cycle into stage 3;
  3. DRAM command scheduler (DCS): per-bank FIFOs (C, B, D); only FIFO heads
     issue; DRAM timing legality enforced; round-robin across banks.

Unlike the centralized schedulers there is no CAM scan: every structure is a
head/length circular FIFO — which is exactly the power/area claim §5.2
audits.

These stage functions are the implementation behind the registered "sms" /
"sms_dash" `MemoryPolicy` objects (see `repro.core.policies.sms`): stages 1+2
form the policy's `tick`, stage 3 its `select`. Every stage is a whole-array
op over all channels at once — no Python channel loop — so trace size and
compile time are independent of `n_channels`.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.params import CLS_HWA, SimConfig, static_bool


def sms_state(cfg: SimConfig) -> Dict[str, Any]:
    C, S, F = cfg.n_channels, cfg.n_src, cfg.fifo_size
    B, D = cfg.n_banks, cfg.dcs_size
    zi = lambda *s: jnp.zeros(s, jnp.int32)
    return {
        # stage 1: per-source FIFOs
        "f_row": zi(C, S, F), "f_bank": zi(C, S, F), "f_birth": zi(C, S, F),
        "f_head": zi(C, S), "f_len": zi(C, S),
        # front_run: length of the front same-(bank,row) run of each FIFO
        # (the next batch), maintained incrementally at push/pop so stage 2
        # never re-gathers the full (C,S,F) FIFO view
        "front_run": zi(C, S),
        # stage 2: batch scheduler
        "drain_src": jnp.full((C,), -1, jnp.int32),
        "drain_left": zi(C),
        "rr_ptr": zi(C),
        "rng2": jnp.arange(1, C + 1, dtype=jnp.uint32) * jnp.uint32(40503),
        # stage 3: per-bank DCS FIFOs
        "d_row": zi(C, B, D), "d_src": zi(C, B, D), "d_birth": zi(C, B, D),
        "d_head": zi(C, B), "d_len": zi(C, B), "rr_bank": zi(C),
    }


def _run_from_head(rows, banks, head, length, F):
    """Front same-(bank,row) run length of one FIFO per channel.

    rows/banks: (C, F) slot arrays; head/length: (C,). Only used on the
    rare pop-exhausted-a-batch path, for the single drained source per
    channel — O(C·F), not O(C·S·F).
    """
    idx = (head[:, None] + jnp.arange(F)) % F
    rows_o = jnp.take_along_axis(rows, idx, axis=-1)
    banks_o = jnp.take_along_axis(banks, idx, axis=-1)
    in_r = jnp.arange(F) < length[:, None]
    eq = (rows_o == rows_o[:, :1]) & (banks_o == banks_o[:, :1]) & in_r
    return jnp.sum(jnp.cumprod(eq.astype(jnp.int32), axis=-1), axis=-1)


def batch_info(cfg: SimConfig, sms: Dict[str, Any], t):
    """(C,S) arrays: batch_len (front same-(bank,row) run) and readiness.

    batch_len is the incrementally-maintained `front_run` counter; only the
    head birth is gathered (O(C·S)), never the full FIFO contents.
    """
    batch_len = sms["front_run"]
    nonempty = sms["f_len"] > 0
    row_changed = batch_len < sms["f_len"]
    head_birth = jnp.take_along_axis(
        sms["f_birth"], sms["f_head"][..., None], axis=-1)[..., 0]  # (C,S)
    aged = nonempty & (t - head_birth >= cfg.batch_age_cap)
    full = sms["f_len"] >= cfg.fifo_size
    ready = nonempty & (row_changed | aged | full)
    return batch_len, ready


def stage1_admit(cfg: SimConfig, st, sms, t):
    """Decentralized admission: every source pushes into its own FIFO."""
    C, S, F = cfg.n_channels, cfg.n_src, cfg.fifo_size
    st = dict(st)
    sms = dict(sms)
    ch = engine.channel_of(cfg, st["pend_bank"])            # (S,)
    sidx = jnp.arange(S)
    flen = sms["f_len"][ch, sidx]
    room = flen < F
    do = st["pend_valid"] & room
    head = sms["f_head"][ch, sidx]
    slot = (head + flen) % F
    new_bank = engine.bank_in_channel(cfg, st["pend_bank"])
    # each source maps to exactly one channel this cycle: one-hot masked
    # writes (no scatters in the hot loop)
    mask_cs = (jnp.arange(C)[:, None] == ch[None, :]) & do[None, :]  # (C,S)
    mask_csf = mask_cs[:, :, None] & \
        (jnp.arange(F)[None, None, :] == slot[None, :, None])     # (C,S,F)
    # front_run: a push extends the front batch only when the whole FIFO is
    # that batch (front_run == f_len) and the new request matches its
    # (bank, row); a push into an empty FIFO starts a run of 1
    fr = sms["front_run"][ch, sidx]
    extend = (fr == flen) & \
        (st["pend_row"] == sms["f_row"][ch, sidx, head]) & \
        (new_bank == sms["f_bank"][ch, sidx, head])
    new_fr = jnp.where(flen == 0, 1, jnp.where(extend, fr + 1, fr))
    sms["front_run"] = jnp.where(mask_cs, new_fr[None, :],
                                 sms["front_run"])
    wr = lambda a, v: jnp.where(mask_csf, v[None, :, None], a)
    sms["f_row"] = wr(sms["f_row"], st["pend_row"])
    sms["f_bank"] = wr(sms["f_bank"], new_bank)
    sms["f_birth"] = wr(sms["f_birth"], st["pend_birth"])
    sms["f_len"] = sms["f_len"] + mask_cs.astype(jnp.int32)
    st["pend_valid"] = st["pend_valid"] & ~do
    return st, sms


def stage2_drain(cfg: SimConfig, pool, st, sms, t):
    """Pick ready batches (SJF w.p. p / RR w.p. 1-p) and drain 1 req/cycle."""
    C, S, F = cfg.n_channels, cfg.n_src, cfg.fifo_size
    B, D = cfg.n_banks, cfg.dcs_size
    sms = dict(sms)
    batch_len, ready = batch_info(cfg, sms, t)

    # --- pick a new batch on idle channels ---
    idle = sms["drain_left"] <= 0
    rng2, u = engine.lcg_step(sms["rng2"])
    sms["rng2"] = rng2
    use_sjf = u < cfg.sjf_prob                              # (C,)
    inflight = (st["emitted"] - st["completed"]).astype(jnp.int32)  # (S,)
    sjf_key = jnp.where(ready, inflight[None, :], 1 << 28)  # (C,S)
    sjf_pick = jnp.argmin(sjf_key, axis=-1)
    rr_off = (jnp.arange(S)[None, :] - sms["rr_ptr"][:, None]) % S
    rr_key = jnp.where(ready, rr_off, 1 << 28)
    rr_pick = jnp.argmin(rr_key, axis=-1)
    pick = jnp.where(use_sjf, sjf_pick, rr_pick)
    # `dash` is a value knob: statically False keeps the block out of the
    # trace entirely (the legacy SMS trace); statically True is the legacy
    # sms_dash trace; a traced/batched knob keeps the block and masks the
    # preemption with the knob itself.
    dash_on = static_bool(cfg.dash)
    if dash_on is not False:
        # SMS-DASH (paper §7 / Usui et al.): an HWA whose frame slack is
        # below its estimated remaining service time preempts the SJF/RR
        # choice; least-slack-first among urgent ready batches.
        has_dl = (pool["src_class"] == CLS_HWA) & (pool["dl_period"] > 0)
        remaining = jnp.maximum(pool["dl_reqs"] - st["period_done"], 0)
        time_left = pool["dl_period"] - jnp.mod(
            t, jnp.maximum(pool["dl_period"], 1))
        slack = time_left.astype(jnp.float32) - \
            remaining.astype(jnp.float32) * cfg.dash_svc_est
        urgent = has_dl & (slack < 0.0) & (remaining > 0)
        urgent_ready = ready & urgent[None, :]
        u_key = jnp.where(urgent_ready, slack[None, :], jnp.float32(1e30))
        u_pick = jnp.argmin(u_key, axis=-1)
        any_urgent = jnp.any(urgent_ready, axis=-1)
        if dash_on is None:
            any_urgent = any_urgent & cfg.dash
        pick = jnp.where(any_urgent, u_pick, pick)
        use_sjf = use_sjf | any_urgent          # don't advance rr on preempt
    any_ready = jnp.any(ready, axis=-1)
    start = idle & any_ready
    sms["drain_src"] = jnp.where(start, pick.astype(jnp.int32),
                                 sms["drain_src"])
    sms["drain_left"] = jnp.where(
        start, batch_len[jnp.arange(C), pick], sms["drain_left"])
    sms["rr_ptr"] = jnp.where(start & ~use_sjf, (pick + 1) % S,
                              sms["rr_ptr"]).astype(jnp.int32)

    # --- drain one request per channel into the DCS ---
    draining = sms["drain_left"] > 0
    s = jnp.clip(sms["drain_src"], 0, S - 1)                # (C,)
    cidx = jnp.arange(C)
    head = sms["f_head"][cidx, s]
    row = sms["f_row"][cidx, s, head]
    bank = sms["f_bank"][cidx, s, head]
    birth = sms["f_birth"][cidx, s, head]
    has_req = sms["f_len"][cidx, s] > 0
    # safety: a desynced drain counter on an empty FIFO must not deadlock
    sms["drain_left"] = jnp.where(draining & ~has_req, 0, sms["drain_left"])
    dcs_room = sms["d_len"][cidx, bank] < D
    do = draining & has_req & dcs_room
    # pop stage-1
    new_head = jnp.where(do, (head + 1) % F, head)
    new_len = sms["f_len"][cidx, s] - do.astype(jnp.int32)
    sms["f_head"] = engine.masked_set(sms["f_head"], s, new_head, do)
    sms["f_len"] = engine.masked_add(sms["f_len"], s, -1, do)
    sms["drain_left"] = sms["drain_left"] - do.astype(jnp.int32)
    # front_run: the pop shortens the front batch by one; when it exhausts
    # the batch with requests left, rescan just this source's FIFO (O(C·F))
    # for the next batch's run length
    fr = sms["front_run"][cidx, s] - do.astype(jnp.int32)
    rescan = do & (fr == 0) & (new_len > 0)
    fr = jnp.where(rescan,
                   _run_from_head(sms["f_row"][cidx, s],
                                  sms["f_bank"][cidx, s],
                                  new_head, new_len, F),
                   fr)
    sms["front_run"] = engine.masked_set(sms["front_run"], s, fr, do)
    # push stage-3
    dslot = (sms["d_head"][cidx, bank] + sms["d_len"][cidx, bank]) % D
    wr = lambda a, v: engine.masked_set2(a, bank, dslot, v, do)
    sms["d_row"] = wr(sms["d_row"], row)
    sms["d_src"] = wr(sms["d_src"], s.astype(jnp.int32))
    sms["d_birth"] = wr(sms["d_birth"], birth)
    sms["d_len"] = engine.masked_add(sms["d_len"], bank, 1, do)
    return st, sms


def stage3_issue(cfg: SimConfig, st, sms, dram, t):
    """DCS: issue from per-bank FIFO heads, RR across eligible banks.

    All channels resolve at once: per-channel picks are independent (each
    touches only its own DCS/DRAM rows) and issue side effects commute.
    """
    C, B, D = cfg.n_channels, cfg.n_banks, cfg.dcs_size
    sms = dict(sms)
    cidx = jnp.arange(C)
    head = sms["d_head"]                                    # (C,B)
    at_head = lambda a: jnp.take_along_axis(a, head[..., None], 2)[..., 0]
    row = at_head(sms["d_row"])                             # (C,B)
    src = at_head(sms["d_src"])
    birth = at_head(sms["d_birth"])
    valid = sms["d_len"] > 0
    elig, lat, is_hit = engine.eligibility(
        cfg, dram, jnp.broadcast_to(jnp.arange(B), (C, B)), row, valid, t)
    rr_key = jnp.where(elig, (jnp.arange(B)[None, :]
                              - sms["rr_bank"][:, None]) % B, 1 << 28)
    pick = jnp.argmin(rr_key, axis=1)                       # (C,)
    at_pick = lambda a: jnp.take_along_axis(a, pick[:, None], 1)[:, 0]
    do = at_pick(elig)
    dram, st = engine.issue_channels(
        cfg, dram, st, do, pick, at_pick(row), at_pick(src), at_pick(birth),
        at_pick(lat), at_pick(is_hit), t)
    head_p = head[cidx, jnp.where(do, pick, 0)]
    sms["d_head"] = engine.masked_set(sms["d_head"], pick, (head_p + 1) % D,
                                      do)
    sms["d_len"] = engine.masked_add(sms["d_len"], pick, -1, do)
    sms["rr_bank"] = jnp.where(do, (pick + 1) % B,
                               sms["rr_bank"]).astype(jnp.int32)
    return st, sms, dram


# ---------------------------------------------------------------------------
# variable-step driver witnesses (ROADMAP "Variable-step driver contract")
# ---------------------------------------------------------------------------

def next_stage_event(cfg: SimConfig, st, sms, dram, t):
    """Earliest cycle > t at which any of the three stages could act.

    Conservative-early like the centralized witnesses: stage 1 fires while
    any pending register has FIFO room; stage 2 fires while any channel is
    draining or could start a batch, plus the age-threshold time at which a
    quiet front batch becomes ready; stage 3 inverts the DRAM timing gates
    on the per-bank DCS heads. The dash urgency pick needs no witness of
    its own — it is recomputed from scratch on every processed cycle and
    only consulted when a drain starts, which is itself witnessed.
    """
    tm = cfg.timing
    INF = jnp.int32(engine.INF_T)
    t1 = t + 1
    # stage 1: a pending register with FIFO room pushes next cycle
    ch = engine.channel_of(cfg, st["pend_bank"])             # (S,)
    room = sms["f_len"][ch, jnp.arange(cfg.n_src)] < cfg.fifo_size
    w1 = jnp.where(jnp.any(st["pend_valid"] & room), t1, INF)
    # stage 2: an active drain moves (or settles) every cycle; an idle
    # channel starts as soon as any batch is ready
    _, ready = batch_info(cfg, sms, t)
    idle = sms["drain_left"] <= 0
    act = jnp.any(~idle) | jnp.any(idle & jnp.any(ready, axis=-1))
    w2 = jnp.where(act, t1, INF)
    # aging: a nonempty, not-yet-ready FIFO turns ready at head_birth + cap
    head_birth = jnp.take_along_axis(
        sms["f_birth"], sms["f_head"][..., None], axis=-1)[..., 0]  # (C,S)
    w_age = jnp.min(jnp.where(
        (sms["f_len"] > 0) & ~ready,
        jnp.maximum(head_birth + cfg.batch_age_cap, t1), INF))
    # stage 3: DCS head issue-eligibility times (inverts the three
    # `engine.eligibility` gates; their inputs are frozen while no issue
    # lands, which the witness itself guarantees for the span)
    at_head = lambda a: jnp.take_along_axis(a, sms["d_head"][..., None],
                                            2)[..., 0]        # (C,B)
    row = at_head(sms["d_row"])
    openv = dram["open_valid"]
    is_hit = openv & (dram["open_row"] == row)
    lat = jnp.where(is_hit, tm.lat_hit,
                    jnp.where(openv, tm.lat_conflict, tm.lat_closed)
                    ).astype(jnp.int32)
    faw_ready = jnp.min(dram["act_ring"], axis=1)[:, None] + tm.t_faw
    tau = jnp.maximum(dram["bank_free"],
                      jnp.where(is_hit, engine.NEG_T, faw_ready))
    tau = jnp.maximum(tau, dram["bus_free"][:, None] - lat)
    tau = jnp.maximum(tau, t1)
    w3 = jnp.min(jnp.where(sms["d_len"] > 0, tau, INF))
    return jnp.minimum(jnp.minimum(w1, w2), jnp.minimum(w_age, w3))


def skip_cycles(sms: Dict[str, Any], k) -> Dict[str, Any]:
    """Replay k skipped cycles of stage-2 state in closed form: the batch
    scheduler draws `rng2` once per cycle unconditionally."""
    sms = dict(sms)
    sms["rng2"] = engine.lcg_skip(sms["rng2"], k)
    return sms


# ---------------------------------------------------------------------------
# invariant-sanitizer hooks (repro.core.validate; traced only when
# cfg.validate_enabled — ROADMAP "Validation & fault-injection contract")
# ---------------------------------------------------------------------------

def check_invariants(cfg: SimConfig, sms: Dict[str, Any], t):
    """Count of violated staged-structure invariants: FIFO/DCS occupancy
    within declared bounds, heads in range, `front_run` matching a full
    recount, a non-negative drain counter, and the stage-2 rng stream at
    its closed-form position (one draw per cycle, ticked or skipped)."""
    C, F, D = cfg.n_channels, cfg.fifo_size, cfg.dcs_size
    n = lambda x: jnp.sum(jnp.asarray(x, jnp.int32))
    bad = n((sms["f_len"] < 0) | (sms["f_len"] > F))
    bad += n((sms["f_head"] < 0) | (sms["f_head"] >= F))
    bad += n((sms["d_len"] < 0) | (sms["d_len"] > D))
    bad += n((sms["d_head"] < 0) | (sms["d_head"] >= D))
    bad += n(sms["drain_left"] < 0)
    bad += n((sms["front_run"] < 0) | (sms["front_run"] > sms["f_len"]))
    bad += n((sms["f_len"] > 0) & (sms["front_run"] == 0))
    run = jax.vmap(lambda r, b, h, l: _run_from_head(r, b, h, l, F),
                   in_axes=(1, 1, 1, 1), out_axes=1)(
        sms["f_row"], sms["f_bank"], sms["f_head"], sms["f_len"])
    bad += n((sms["f_len"] > 0) & (run != sms["front_run"]))
    rng0 = jnp.arange(1, C + 1, dtype=jnp.uint32) * jnp.uint32(40503)
    bad += n(sms["rng2"] != engine.lcg_skip(rng0, t + 1))
    return bad


def audit_skip(cfg: SimConfig, st, sms: Dict[str, Any], dram, t, t_new):
    """Would-fire lateness predicates for a jumped span, re-derived from the
    stage conditions at the last skipped cycle u (stage state is frozen over
    a span; only the age predicate is t-dependent, and it is monotone).
    Stage-1 pushes report as late_admission, stage-2 batch events as
    late_boundary, stage-3 DCS-head eligibility as late_issue."""
    u = t_new - 1
    skipped = t_new - t > 1
    ch = engine.channel_of(cfg, st["pend_bank"])
    room = sms["f_len"][ch, jnp.arange(cfg.n_src)] < cfg.fifo_size
    s1 = jnp.any(st["pend_valid"] & room)
    _, ready = batch_info(cfg, sms, u)
    idle = sms["drain_left"] <= 0
    s2 = jnp.any(~idle) | jnp.any(idle & jnp.any(ready, axis=-1))
    at_head = lambda a: jnp.take_along_axis(a, sms["d_head"][..., None],
                                            2)[..., 0]
    row = at_head(sms["d_row"])
    valid = sms["d_len"] > 0
    banks = jnp.broadcast_to(jnp.arange(cfg.n_banks), row.shape)
    elig, _, _ = engine.eligibility(cfg, dram, banks, row, valid, u)
    b = lambda x: (skipped & x).astype(jnp.int32)
    return {"late_admission": b(s1), "late_boundary": b(s2),
            "late_issue": b(jnp.any(elig))}
