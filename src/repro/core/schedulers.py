"""Centralized request-buffer substrate for `MemoryPolicy` implementations.

The FR-FCFS family (FR-FCFS, ATLAS, PAR-BS, TCM, BLISS, SQUASH-prio, ...)
shares one structure — a per-channel CAM-style request buffer scored every
cycle — exactly the monolithic design SMS decomposes. This module provides
that substrate as `CentralizedPolicy`, a base class for the protocol in
`repro.core.policy`: subclasses (one module each under
`repro.core.policies/`) override

    extra_state(cfg)                       policy-private state arrays
    boundary_pred(cfg, pool, st, buf, t)   scalar bool: run boundary_tick?
                                           (None = policy has no boundary)
    boundary_tick(cfg, pool, st, buf, t)   epoch/quantum/batch maintenance,
                                           executed under `lax.cond`
    policy_tick(cfg, pool, st, buf, t)     cheap per-cycle maintenance
    score(cfg, pool, buf, is_hit, t)       (C, E) int32 lexicographic score
    on_admit(cfg, pool, st, buf, do, slot, src, t)   per-admission hook
    on_issue(cfg, pool, buf, do, pick, src, t)       per-issue hook (buf is
                                                     the PRE-clear buffer)

Hot-loop contract (see ROADMAP "hot-loop rules"): anything that sorts or
ranks belongs in `boundary_tick`. A predicate that depends only on the
scan's scalar cycle counter `t` stays unbatched under `vmap`, so the cond
branch genuinely executes once per epoch; a data-dependent predicate
degrades to `select` under `vmap` but still keeps the sort out of the
unbatched per-cycle jaxpr. The default `score` adds a cached per-source
priority (`buf["pri_src"]`, computed by `boundary_tick`) to the FR-FCFS
base score, so no subclass ranks in `score`.

Scores are lexicographic integers:

    [policy bits 22+] [rank 15..20] [row-hit 14] [age 0..13]

Buffer shapes: (C, E). Admission is one request per channel per cycle
(single MC ingress port); half the entries are reserved for CPU sources
(the paper's anti-starvation provisioning, §4): GPU occupancy is tracked
by the incrementally-maintained `gpu_occ` counter (admit +1, issue -1)
instead of an O(C·E) reduction each cycle. Admission and issue are
expressed as whole-(C, ...) array ops — channels never appear as a Python
loop, so trace size is independent of `n_channels`.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core import energy, engine, params, telemetry, validate
from repro.core.params import SimConfig

AGE_CAP = (1 << 14) - 1
HIT_BIT = 1 << 14
RANK_SHIFT = 15
POL_BIT = 1 << 22


def buffer_state(cfg: SimConfig) -> Dict[str, Any]:
    """The shared CAM buffer; policy-private arrays live in extra_state.

    `gpu_occ` mirrors `sum(valid & is_gpu_src[src])` per channel — admit
    increments it, issue decrements it — so the CPU-reservation check never
    re-scans the buffer.
    """
    C, E = cfg.n_channels, cfg.buf_entries
    z = lambda dt: jnp.zeros((C, E), dt)
    return {
        "valid": z(bool), "src": z(jnp.int32), "bank": z(jnp.int32),
        "row": z(jnp.int32), "birth": z(jnp.int32), "marked": z(bool),
        "gpu_occ": jnp.zeros((C,), jnp.int32),
    }


def rank_pos(key: jax.Array) -> jax.Array:
    """rank position of each element under ascending sort (0 = smallest)."""
    return jnp.argsort(jnp.argsort(key)).astype(jnp.int32)


def base_score(cfg: SimConfig, buf, is_hit, t) -> jax.Array:
    """FR-FCFS core: row hit above age. (C, E) int32."""
    age = jnp.clip(t - buf["birth"], 0, AGE_CAP)
    return is_hit.astype(jnp.int32) * HIT_BIT + age


def admit(cfg: SimConfig, pool, st, buf, t, key=None):
    """One admission per channel per cycle; lowest-key pending request wins
    (default key: birth, i.e. oldest first).

    Enforces the CPU reservation: GPU sources are blocked while they hold
    >= gpu_cap entries in that channel's buffer (tracked by the `gpu_occ`
    counter). Sources map to exactly one channel, so all channels admit
    independently in one batched op.

    Returns (st, buf, do, slot, src): per-channel admission outcome for
    `on_admit` hooks.
    """
    S, C = cfg.n_src, cfg.n_channels
    is_gpu_src = pool["is_gpu"]
    st = dict(st)
    buf = dict(buf)
    cidx = jnp.arange(C)
    ch = engine.channel_of(cfg, st["pend_bank"])                # (S,)
    gpu_ok = buf["gpu_occ"] < cfg.gpu_cap
    cand = st["pend_valid"][None, :] & (ch[None, :] == cidx[:, None]) \
        & (gpu_ok[:, None] | ~is_gpu_src[None, :])              # (C, S)
    has_free = ~jnp.all(buf["valid"], axis=1)                   # (C,)
    key = st["pend_birth"] if key is None else key
    key = jnp.where(cand, key[None, :], jnp.int32(2**30))
    s = jnp.argmin(key, axis=1)                                 # (C,)
    do = cand[cidx, s] & has_free
    slot = jnp.argmin(buf["valid"], axis=1)                     # first free
    wr = lambda a, v: engine.masked_set(a, slot, v, do)
    buf["valid"] = wr(buf["valid"], True)
    buf["src"] = wr(buf["src"], s.astype(jnp.int32))
    buf["bank"] = wr(buf["bank"], engine.bank_in_channel(cfg,
                                                         st["pend_bank"][s]))
    buf["row"] = wr(buf["row"], st["pend_row"][s])
    buf["birth"] = wr(buf["birth"], st["pend_birth"][s])
    buf["marked"] = wr(buf["marked"], False)
    buf["gpu_occ"] = buf["gpu_occ"] + \
        (do & is_gpu_src[s]).astype(jnp.int32)
    taken = jnp.any((jnp.arange(S) == s[:, None]) & do[:, None], axis=0)
    st["pend_valid"] = st["pend_valid"] & ~taken
    return st, buf, do, slot, s.astype(jnp.int32)


class CentralizedPolicy:
    """`MemoryPolicy` base for single-stage CAM-buffer schedulers.

    The per-cycle step is split in two: `policy_tick` runs every cycle and
    must stay cheap (no sorts, no O(C·E) reductions for incrementally
    maintainable state); `boundary_tick` holds the epoch/quantum/batch
    maintenance — ranking sorts included — and executes under `lax.cond`
    gated on `boundary_pred`.
    """

    name = "centralized"
    variant_of = None

    # keys `boundary_tick` may WRITE. The cond's operands/outputs are
    # restricted to these (everything else is read through the closure), so
    # the per-cycle step never copies or selects untouched (C, E) arrays
    # through the conditional. Keep this to the small (S,)-shaped state.
    boundary_keys: tuple = ()

    # -- cross-policy stacking contract (see `make_stacked_step`) -----------
    # A stackable policy agrees to run with its state padded to the family
    # union schema (extra keys from sibling policies present but zero) and
    # with `configure` leaving cfg untouched. Opt out with `stackable =
    # False` for state that cannot be padded (or schema-colliding keys).
    stackable: bool = True
    # buf keys the tick-side hooks (on_admit/pre_tick/boundary_tick/
    # policy_tick) may WRITE. None = `boundary_keys`. The stacked step
    # re-stacks only the union of these across the family; an undeclared
    # write is silently dropped on the stacked path (and caught by the
    # golden-digest equivalence test).
    stacked_tick_keys: tuple = None
    # buf keys `on_issue` may WRITE (default: none).
    stacked_issue_keys: tuple = ()

    # -- per-policy hooks --------------------------------------------------
    def extra_state(self, cfg: SimConfig) -> Dict[str, Any]:
        return {}

    def pre_tick(self, cfg: SimConfig, pool, st, buf, t):
        """Per-cycle maintenance that must run BEFORE the boundary gate
        (state that `boundary_pred`/`boundary_tick` read). Sort-free."""
        return buf

    def boundary_pred(self, cfg: SimConfig, pool, st, buf, t):
        """Scalar bool gating `boundary_tick`; None = no boundary work.

        Predicates that depend only on `t` stay unbatched under `vmap`, so
        the gated branch truly runs once per epoch.
        """
        return None

    def boundary_tick(self, cfg: SimConfig, pool, st, buf, t):
        """Cond-gated maintenance: rank recomputes, shuffles. May read any
        state but only write `boundary_keys`."""
        return buf

    def policy_tick(self, cfg: SimConfig, pool, st, buf, t):
        """Unconditional per-cycle maintenance; keep it sort-free."""
        return buf

    def score(self, cfg: SimConfig, pool, buf, is_hit, t) -> jax.Array:
        """Default: cached per-source priority + FR-FCFS base score."""
        s = base_score(cfg, buf, is_hit, t)
        if "pri_src" in buf:
            s = engine.small_lookup(buf["pri_src"], buf["src"]) + s
        return s

    def on_admit(self, cfg: SimConfig, pool, st, buf, do, slot, src, t):
        """Per-admission accounting ((C,) vectors, after the buffer write)."""
        return buf

    def on_issue(self, cfg: SimConfig, pool, buf, do, pick, src, t):
        """Per-issue accounting. `buf` is PRE-clear: entry `pick` still
        holds the issued request's fields."""
        return buf

    def admit_key(self, cfg: SimConfig, pool, st, buf, t):
        """(S,) admission ordering key, lowest first (default: oldest)."""
        return st["pend_birth"]

    def next_boundary(self, cfg: SimConfig, pool, st, buf, t):
        """Scalar: earliest cycle > t at which `boundary_pred` could fire or
        any other per-cycle policy state could change in a way the generic
        witnesses don't cover (e.g. a t-dependent urgency flip). None = no
        boundary machinery. Early is safe, late is a correctness bug (see
        ROADMAP "Variable-step driver contract")."""
        return None

    # -- variable-step driver witness (see `policy.make_skip_step`) ---------
    def next_event(self, cfg: SimConfig, pool, st, buf, dram, t):
        """Earliest cycle > t at which this policy's half of the cycle could
        do anything: admit a pending request, issue a buffered one, or run
        boundary maintenance. Evaluated on post-cycle-t state."""
        te = next_admission(cfg, pool, st, buf, t)
        te = jnp.minimum(te, next_issue_ready(cfg, buf, dram, t))
        nb = self.next_boundary(cfg, pool, st, buf, t)
        if nb is not None:
            te = jnp.minimum(te, nb)
        return te

    # -- invariant-sanitizer hooks (repro.core.validate; measurement-only,
    # traced only when cfg.validate_enabled — see ROADMAP "Validation &
    # fault-injection contract") ------------------------------------------
    def queued_requests(self, cfg: SimConfig, buf):
        """Requests held in policy structures (total-flow conservation)."""
        return jnp.sum(buf["valid"].astype(jnp.int32))

    def check_invariants(self, cfg: SimConfig, pool, st, buf, t):
        """Count of violated buffer invariants: the `gpu_occ` mirror counter
        matches a recount of GPU-held entries, occupancy stays within
        [0, E], and marks only sit on valid entries. Subclasses extend with
        their own mirror-counter recounts (e.g. PAR-BS `msub`/`grank`)."""
        occ = jnp.sum((buf["valid"] & pool["is_gpu"][buf["src"]])
                      .astype(jnp.int32), axis=1)
        bad = jnp.sum((occ != buf["gpu_occ"]).astype(jnp.int32))
        bad += jnp.sum(((buf["gpu_occ"] < 0) |
                        (buf["gpu_occ"] > cfg.buf_entries)).astype(jnp.int32))
        bad += jnp.sum((buf["marked"] & ~buf["valid"]).astype(jnp.int32))
        return bad

    def audit_skip(self, cfg: SimConfig, pool, st, buf, dram, t, t_new):
        """Would-fire lateness predicates for a jumped span: independent
        inline re-derivations of admission/issue readiness (never the
        witness formulas themselves), evaluated at the last skipped cycle
        `u` — valid because readiness is monotone in t over frozen span
        state. `next_boundary` is safe to reuse: it was evaluated at t by
        the driver, so `nb < t_new` can only mean the driver ignored it."""
        u = t_new - 1
        skipped = t_new - t > 1
        ch = engine.channel_of(cfg, st["pend_bank"])
        gpu_ok = buf["gpu_occ"] < cfg.gpu_cap
        has_free = ~jnp.all(buf["valid"], axis=1)
        adm = jnp.any(st["pend_valid"] & has_free[ch] &
                      (gpu_ok[ch] | ~pool["is_gpu"]))
        elig, _, _ = eligibility_grid(cfg, buf, dram, u)
        out = {"late_admission": (skipped & adm).astype(jnp.int32),
               "late_issue": (skipped & jnp.any(elig)).astype(jnp.int32)}
        nb = self.next_boundary(cfg, pool, st, buf, t)
        if nb is not None:
            out["late_boundary"] = (skipped & (nb < t_new)).astype(jnp.int32)
        return out

    # -- MemoryPolicy protocol ---------------------------------------------
    def configure(self, cfg: SimConfig) -> SimConfig:
        return cfg

    def init_state(self, cfg: SimConfig) -> Dict[str, Any]:
        return {**buffer_state(cfg), **self.extra_state(cfg)}

    def tick_hooks(self, cfg: SimConfig, pool, st, buf, do, slot, src, t):
        """Everything policy-specific between admission and selection:
        per-admission accounting, cheap maintenance, the cond-gated boundary
        work. The stacked step dispatches here per policy slice."""
        buf = self.on_admit(cfg, pool, st, buf, do, slot, src, t)
        buf = self.pre_tick(cfg, pool, st, buf, t)
        pred = self.boundary_pred(cfg, pool, st, buf, t)
        if pred is not None:
            keys = self.boundary_keys

            def run(sub):
                new = self.boundary_tick(cfg, pool, st, {**buf, **sub}, t)
                return {k: new[k] for k in keys}

            sub = jax.lax.cond(pred, run, lambda s: s,
                               {k: buf[k] for k in keys})
            buf = {**buf, **sub}
        buf = self.policy_tick(cfg, pool, st, buf, t)
        return buf

    def tick(self, cfg: SimConfig, pool, st, buf, t):
        st, buf, do, slot, src = admit(
            cfg, pool, st, buf, t,
            key=self.admit_key(cfg, pool, st, buf, t))
        buf = self.tick_hooks(cfg, pool, st, buf, do, slot, src, t)
        return st, buf

    def select(self, cfg: SimConfig, pool, st, buf, dram, t):
        """Pick + issue at most one request per channel (all channels at
        once; cross-channel state only meets in commutative scatter-adds)."""
        elig, lat, is_hit = eligibility_grid(cfg, buf, dram, t)
        score = self.score(cfg, pool, buf, is_hit, t)
        score = jnp.where(elig, score, -1)
        st, dram, do, pick, src = issue_picked(cfg, st, buf, dram, score,
                                               lat, is_hit, t)
        buf = self.on_issue(cfg, pool, buf, do, pick, src, t)
        buf = clear_picked(cfg, pool, buf, do, pick, src)
        return st, buf, dram


def eligibility_grid(cfg: SimConfig, buf, dram, t):
    """Per-entry issue legality for every channel: (C, E) elig/lat/is_hit."""
    return engine.eligibility(cfg, dram, buf["bank"], buf["row"],
                              buf["valid"], t)


def issue_picked(cfg: SimConfig, st, buf, dram, score, lat, is_hit, t):
    """argmax the masked score per channel and commit the issue to DRAM.

    Returns (st, dram, do, pick, src); `buf` is untouched (still pre-clear)
    so `on_issue` hooks can read the issued entry's fields.
    """
    pick = jnp.argmax(score, axis=1)                            # (C,)
    at_pick = lambda a: jnp.take_along_axis(a, pick[:, None], 1)[:, 0]
    do = at_pick(score) >= 0
    src = at_pick(buf["src"])
    dram, st = engine.issue_channels(
        cfg, dram, st, do, at_pick(buf["bank"]), at_pick(buf["row"]),
        src, at_pick(buf["birth"]), at_pick(lat), at_pick(is_hit), t)
    return st, dram, do, pick, src


def clear_picked(cfg: SimConfig, pool, buf, do, pick, src):
    """Free the issued entries and settle the GPU-occupancy counter."""
    buf = dict(buf)
    clear = lambda a: engine.masked_set(a, pick, False, do)
    buf["valid"] = clear(buf["valid"])
    buf["marked"] = clear(buf["marked"])
    buf["gpu_occ"] = buf["gpu_occ"] - \
        (do & pool["is_gpu"][src]).astype(jnp.int32)
    return buf


# ---------------------------------------------------------------------------
# variable-step witnesses for the centralized substrate (conservative-early;
# see ROADMAP "Variable-step driver contract"). Both are evaluated on
# post-cycle state; any state they read is frozen until one of the family of
# witnesses fires, which is what makes the returned times trustworthy.
# ---------------------------------------------------------------------------

def next_admission(cfg: SimConfig, pool, st, buf, t):
    """t+1 if any pending request could be admitted next cycle, else INF.

    Admissibility can only change via events other witnesses already cover
    (a new pending request = source event; a freed slot or GPU-occupancy
    drop = issue event), so a currently-blocked pending register stays
    blocked for the whole span."""
    ch = engine.channel_of(cfg, st["pend_bank"])                 # (S,)
    gpu_ok = buf["gpu_occ"] < cfg.gpu_cap                        # (C,)
    has_free = ~jnp.all(buf["valid"], axis=1)                    # (C,)
    ok = st["pend_valid"] & has_free[ch] & \
        (gpu_ok[ch] | ~pool["is_gpu"])
    return jnp.where(jnp.any(ok), t + 1, jnp.int32(engine.INF_T))


def next_issue_ready(cfg: SimConfig, buf, dram, t):
    """Earliest cycle > t at which any buffered entry becomes issue-eligible.

    Inverts `engine.eligibility`'s three timing gates per entry — bank
    ready, tFAW window, bus ready — whose inputs (bank_free/act_ring/
    bus_free/open_row) are all frozen while no issue lands. Every policy's
    score is non-negative for eligible entries, so first-eligibility time
    is exactly first-issue time (and if a future policy ever suppressed an
    eligible entry, an early witness merely processes a no-op cycle)."""
    tm = cfg.timing
    take = lambda a: jnp.take_along_axis(a, buf["bank"], 1)      # (C, E)
    openv = take(dram["open_valid"])
    is_hit = openv & (take(dram["open_row"]) == buf["row"])
    lat = jnp.where(is_hit, tm.lat_hit,
                    jnp.where(openv, tm.lat_conflict, tm.lat_closed)
                    ).astype(jnp.int32)
    faw_ready = jnp.min(dram["act_ring"], axis=1)[:, None] + tm.t_faw
    tau = jnp.maximum(take(dram["bank_free"]),
                      jnp.where(is_hit, engine.NEG_T, faw_ready))
    tau = jnp.maximum(tau, dram["bus_free"][:, None] - lat)
    tau = jnp.maximum(tau, t + 1)
    return jnp.min(jnp.where(buf["valid"], tau, jnp.int32(engine.INF_T)))


# ---------------------------------------------------------------------------
# Stacked cross-policy execution: the whole CentralizedPolicy family as ONE
# scan step / ONE XLA program.
#
# The centralized policies share the buffer layout and the engine half of
# the cycle; they differ only in the hook bodies. So: pad every policy's
# state to the union schema, stack the states on a leading P axis, and per
# cycle run the policy-independent work (source/completion ticks, admission,
# eligibility, issue, clear) ONCE, vmapped over the policy axis, while the
# policy-specific hooks dispatch on the per-policy index over slices of the
# stacked state.
#
# Why the dispatch is per-slice (trace-time index) and not a traced
# `lax.switch` under `vmap`: with the policy index batched, jax's cond/switch
# batching rule inlines ALL branches and select_n's the results — including
# dissolving each branch's *nested* boundary `lax.cond` even when its
# predicate depends only on the scalar cycle counter (measured on the pinned
# jax 0.4.37). That would run every policy's ranking sort every cycle for
# every slice: O(P^2) hook work and a direct violation of hot-loop rule 1.
# Dispatching on the concrete per-policy index keeps exactly one hook body
# per slice in the trace and keeps every t-only boundary predicate unbatched
# (a genuine cond), while the whole family still compiles as one program.
# ---------------------------------------------------------------------------


def stacked_union_state(cfg: SimConfig, pols) -> list:
    """Per-policy init states padded to the family union schema.

    Returns a list of dicts (same keys, same shapes/dtypes) ready to stack
    on a leading P axis. A key claimed by two policies with different
    shape/dtype is a schema collision and refuses to stack.
    """
    states = [p.init_state(cfg) for p in pols]
    union: Dict[str, Any] = {}
    owner: Dict[str, str] = {}
    for p, s in zip(pols, states):
        for k, v in s.items():
            if k in union:
                if union[k].shape != v.shape or union[k].dtype != v.dtype:
                    raise ValueError(
                        f"stacked schema collision on {k!r}: "
                        f"{owner[k]} has {union[k].shape}/{union[k].dtype}, "
                        f"{p.name} has {v.shape}/{v.dtype}")
            else:
                union[k] = jnp.zeros(v.shape, v.dtype)
                owner[k] = p.name
    return [{**union, **s} for s in states]


def _stack_trees(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _slice_tree(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def make_stacked_step(cfg: SimConfig, pols, pool, active, cfgs=None,
                      knobs=None):
    """One simulator cycle for P stacked centralized policies.

    The carry is the usual (st, buf, dram) triple with every leaf carrying a
    leading P axis (buf padded to the union schema). Policy-independent work
    runs once, vmapped over P; `admit_key`/`tick_hooks`/`score`/`on_issue`
    dispatch per policy slice, and only the union of each policy family's
    declared write-sets is re-stacked — untouched padding rides through the
    carry unchanged.

    Knob-grid extension (both default to the legacy trace when None):
    `cfgs` supplies a per-slice config view — e.g. `params.bind`ed
    BoundConfigs carrying per-slice period overrides and knob slices — for
    the per-slice hook dispatch; `knobs` is the variant-stacked Knobs
    pytree, vmapped into the two pieces of shared engine work that read
    value knobs (admission's gpu_cap, the power-down idle threshold).
    """
    P = len(pols)
    cfgs = list(cfgs) if cfgs is not None else [cfg] * P
    tick_union = sorted(set().union(*(
        p.stacked_tick_keys if p.stacked_tick_keys is not None
        else p.boundary_keys for p in pols)))
    issue_union = sorted(set().union(*(p.stacked_issue_keys for p in pols)))
    vP = jax.vmap

    def step(carry, t):
        st, buf, dram = carry
        if cfg.telemetry_enabled:
            with jax.named_scope("step.telemetry"):
                snap = vP(telemetry.snapshot)(st, buf, dram)
        with jax.named_scope("step.engine"):
            st, dram = vP(lambda s, d: engine.completions_tick(s, d, t)
                          )(st, dram)
            if knobs is None:
                dram = vP(lambda d: energy.background_tick(cfg, d, t))(dram)
            else:
                dram = vP(lambda d, kn: energy.background_tick(
                    params.bind(cfg, kn), d, t))(dram, knobs)
            st = vP(lambda s: engine.deadline_tick(cfg, pool, s, t))(st)
            st = vP(lambda s: engine.source_tick(cfg, pool, s, active, t)
                    )(st)
        with jax.named_scope("step.admit"):
            # policy-ordered key per slice, one merged admit
            key = jnp.stack([
                p.admit_key(cfgs[i], pool, _slice_tree(st, i),
                            _slice_tree(buf, i), t)
                for i, p in enumerate(pols)])
            if knobs is None:
                st, buf, do, slot, src = vP(
                    lambda s, b, k: admit(cfg, pool, s, b, t, key=k)
                    )(st, buf, key)
            else:
                st, buf, do, slot, src = vP(
                    lambda s, b, k, kn: admit(params.bind(cfg, kn), pool, s,
                                              b, t, key=k)
                    )(st, buf, key, knobs)
            new = [p.tick_hooks(cfgs[i], pool, _slice_tree(st, i),
                                _slice_tree(buf, i), do[i], slot[i], src[i],
                                t)
                   for i, p in enumerate(pols)]
            buf = {**buf, **{k: jnp.stack([n[k] for n in new])
                             for k in tick_union}}
        # selection: merged eligibility/issue, per-slice score + on_issue
        with jax.named_scope("step.select"):
            with jax.named_scope("select.eligibility"):
                elig, lat, is_hit = vP(
                    lambda b, d: eligibility_grid(cfg, b, d, t))(buf, dram)
            with jax.named_scope("select.score"):
                score = jnp.stack([
                    p.score(cfgs[i], pool, _slice_tree(buf, i), is_hit[i], t)
                    for i, p in enumerate(pols)])
                score = jnp.where(elig, score, -1)
            with jax.named_scope("select.issue"):
                st, dram, do, pick, src = vP(
                    lambda s, b, d, sc, la, hi: issue_picked(
                        cfg, s, b, d, sc, la, hi, t)
                )(st, buf, dram, score, lat, is_hit)
                if issue_union:
                    new = [p.on_issue(cfgs[i], pool, _slice_tree(buf, i),
                                      do[i], pick[i], src[i], t)
                           for i, p in enumerate(pols)]
                    buf = {**buf, **{k: jnp.stack([n[k] for n in new])
                                     for k in issue_union}}
            with jax.named_scope("select.clear"):
                buf = vP(lambda b, d, pk, sr: clear_picked(cfg, pool, b, d,
                                                           pk, sr)
                         )(buf, do, pick, src)
        if cfg.telemetry_enabled:
            # policy-independent accrual (no value knobs read): vmap over
            # P like the engine work rather than dispatching per slice
            with jax.named_scope("step.telemetry"):
                dram = vP(lambda sn, s, b, d: telemetry.tick_accrue(
                    cfg, pool, sn, s, b, d, t))(snap, st, buf, dram)
        if cfg.validate_enabled:
            # conservation laws dispatch per slice like the other hooks
            # (policy invariants differ per policy object)
            with jax.named_scope("step.validate"):
                vio = jnp.stack([
                    _slice_tree(dram, i)["viol"] + validate.tick_counts(
                        cfgs[i], pool, p, _slice_tree(st, i),
                        _slice_tree(buf, i), _slice_tree(dram, i), t)
                    for i, p in enumerate(pols)])
                dram = {**dram, "viol": vio}
        return (st, buf, dram), None

    return step


def make_stacked_skip_step(cfg: SimConfig, pols, pool, active, cfgs=None,
                           knobs=None):
    """Variable-step body for the stacked family (see `policy.make_skip_step`
    for the single-policy contract).

    All P slices share one cycle counter, so a span ends at the MINIMUM
    witness across slices — every slice is processed at every event any
    slice has, which keeps each slice bit-identical to its ticked run (extra
    processed cycles are no-ops by the conservative-early rule) at the cost
    of a lower skip ratio than per-policy execution. The shared witnesses
    (engine sources/completions, admission, issue readiness) vmap over P —
    computing them per slice would multiply the dominant witness cost by
    the family size; only the cheap policy-specific `next_boundary`
    dispatches per slice at trace time like the other hooks.
    """
    if not all(hasattr(p, "next_event") for p in pols):
        return None
    step = make_stacked_step(cfg, pols, pool, active, cfgs=cfgs, knobs=knobs)
    cfgs = list(cfgs) if cfgs is not None else [cfg] * len(pols)
    vP = jax.vmap

    def skip_body(carry, t, t_end):
        carry, _ = step(carry, t)
        st, buf, dram = carry
        with jax.named_scope("step.skip"):
            te = jnp.min(vP(lambda s: engine.next_source_event(
                cfg, pool, s, active, t))(st))
            te = jnp.minimum(te, jnp.min(vP(
                lambda d: engine.next_completion(d, t))(dram)))
            if knobs is None:
                te = jnp.minimum(te, jnp.min(vP(
                    lambda s, b: next_admission(cfg, pool, s, b, t)
                    )(st, buf)))
            else:
                # admission readiness reads gpu_cap, a value knob — thread
                # the per-slice knob point through the vmapped witness
                te = jnp.minimum(te, jnp.min(vP(
                    lambda s, b, kn: next_admission(params.bind(cfg, kn),
                                                    pool, s, b, t)
                    )(st, buf, knobs)))
            te = jnp.minimum(te, jnp.min(vP(
                lambda b, d: next_issue_ready(cfg, b, d, t))(buf, dram)))
            for i, p in enumerate(pols):
                nb = p.next_boundary(cfgs[i], pool, _slice_tree(st, i),
                                     _slice_tree(buf, i), t)
                if nb is not None:
                    te = jnp.minimum(te, nb)
            t_new = jnp.minimum(te, t_end)
            k = t_new - t - 1
            st = vP(lambda s: engine.skip_sources(cfg, pool, s, active, k)
                    )(st)
            if cfg.telemetry_enabled:
                # before energy.skip_accrue (pre-span pd_down); the
                # power-down entry threshold is a value knob, so bind per
                # slice on grids
                with jax.named_scope("step.telemetry"):
                    if knobs is None:
                        dram = vP(lambda s, d: telemetry.skip_accrue(
                            cfg, pool, s, d, t, t_new))(st, dram)
                    else:
                        dram = vP(lambda s, d, kn: telemetry.skip_accrue(
                            params.bind(cfg, kn), pool, s, d, t, t_new)
                            )(st, dram, knobs)
            if knobs is None:
                dram = vP(lambda d: energy.skip_accrue(cfg, d, t, t_new)
                          )(dram)
            else:
                dram = vP(lambda d, kn: energy.skip_accrue(
                    params.bind(cfg, kn), d, t, t_new))(dram, knobs)
        if cfg.validate_enabled:
            with jax.named_scope("step.validate"):
                vio = jnp.stack([
                    _slice_tree(dram, i)["viol"] + validate.span_counts(
                        cfgs[i], pool, p, _slice_tree(st, i),
                        _slice_tree(buf, i), _slice_tree(dram, i), active,
                        t, t_new)
                    for i, p in enumerate(pols)])
                dram = {**dram, "viol": vio}
        return (st, buf, dram), t_new

    return skip_body
