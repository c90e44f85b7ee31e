"""Hot-loop structural invariants for the per-cycle step.

The perf contract of the cond-gated scheduler refactor, checked at the
jaxpr level so a regression fails loudly instead of silently re-inflating
the trace:

  * sort primitives (argsort ranking, remark sorts) may appear ONLY inside
    `cond` branches of the per-cycle step for every centralized policy —
    never unconditionally;
  * the ranked policies (atlas/tcm) actually HAVE their sorts behind a
    cond (the check isn't vacuous), while PAR-BS — reformulated to the
    amortized pairwise-rank form — has no sort primitive at all;
  * the stacked step looks up its small per-bank and per-source tables in
    `select.eligibility` and `select.score` with one-hot selects, never a
    gather;
  * the SMS stages read their small FIFO and DCS tables with one-hot
    selects too: no gather and no cumprod under `sms.stage1`-`3`;
  * the scan carry holds only cycle-varying state: the read-only workload
    parameters `_pool`/`_active` are closed over, not carried;
  * the refactor is bit-identical: the golden digests for atlas/parbs/tcm
    (captured pre-refactor) still match.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import golden, params, validate
from repro.core import policy as policy_api
from repro.core import schedulers
from repro.core import simulator as sim
from repro.core.params import Knobs, SimConfig
from repro.core.schedulers import CentralizedPolicy

CFG = SimConfig(n_cpu=3, n_gpu=1, n_channels=2, buf_entries=24, fifo_size=5,
                dcs_size=3)

SORT_PRIMS = {"sort"}


def _centralized_names():
    return [n for n in policy_api.names()
            if isinstance(policy_api.get(n), CentralizedPolicy)]


def _dummy_pool(cfg):
    S = cfg.n_src
    pool = {k: jnp.zeros((S,), jnp.float32)
            for k in ("mpki", "inst_per_miss", "rbl")}
    pool.update(blp=jnp.ones((S,), jnp.int32),
                is_gpu=jnp.zeros((S,), bool))
    return sim.prepare_pool(pool, (S,))


# jaxpr-walking helpers live in repro.compat (the Jaxpr/ClosedJaxpr types
# moved out of jax.core; compat resolves the right location per jax version)
_walk_prims = compat.walk_primitives


def _step_jaxpr(policy_name, base_cfg=CFG):
    cfg, pol, carry = sim._init(base_cfg, policy_name)
    pool = _dummy_pool(cfg)
    active = jnp.ones((cfg.n_src,), bool)
    step = policy_api.make_step(cfg, pol, pool, active)
    return jax.make_jaxpr(step)(carry, jnp.int32(5))


@pytest.mark.parametrize("policy_name", _centralized_names())
def test_no_unconditional_sorts_in_step(policy_name):
    """Per-cycle jaxpr: sort ops only inside cond branches."""
    jx = _step_jaxpr(policy_name)
    uncond = [p for p, in_cond in _walk_prims(jx.jaxpr)
              if p in SORT_PRIMS and not in_cond]
    assert not uncond, (
        f"{policy_name}: {len(uncond)} unconditional sort op(s) in the "
        f"per-cycle step — ranking belongs in boundary_tick behind cond")


@pytest.mark.parametrize("policy_name", ["atlas", "tcm"])
def test_ranked_policies_sort_inside_cond(policy_name):
    """Non-vacuity: the ranked policies do sort, behind the boundary cond."""
    jx = _step_jaxpr(policy_name)
    gated = [p for p, in_cond in _walk_prims(jx.jaxpr)
             if p in SORT_PRIMS and in_cond]
    assert gated, f"{policy_name}: expected ranking sorts inside cond"


def test_parbs_step_is_sort_free():
    """PAR-BS batch-boundary residue fix: the amortized-rank form computes
    source priority by pairwise comparison counts, so its step jaxpr has NO
    sort primitive at all — gated or not — and no data-dependent cond is
    left on the stacked path for it."""
    jx = _step_jaxpr("parbs")
    sorts = [p for p, _ in _walk_prims(jx.jaxpr) if p in SORT_PRIMS]
    assert not sorts, f"parbs: {len(sorts)} sort op(s) — residue regressed"


GATHER_FREE_SCOPES = {"select.eligibility", "select.score"}


def _scoped_prims(jaxpr, outer=""):
    """Yield (primitive_name, full name stack) over all nested jaxprs."""
    for eqn in jaxpr.eqns:
        stack = "/".join(filter(None, (outer,
                                       str(eqn.source_info.name_stack))))
        yield eqn.primitive.name, stack
        for v in eqn.params.values():
            for sub in compat.sub_jaxprs(v):
                yield from _scoped_prims(sub, stack)


@pytest.mark.parametrize("policy_name", _centralized_names())
def test_stacked_select_lookups_build_no_gather(policy_name):
    """The bank lookups of `engine.eligibility` and the per-source priority
    lookup of `CentralizedPolicy.score` go through `engine.small_lookup`:
    the stacked step has no gather under either scope, while gathers stay
    elsewhere in it (the walk sees them)."""
    pols, carry = sim._init_stacked(CFG, (policy_name,))
    body = schedulers.make_stacked_step(
        CFG, pols, _dummy_pool(CFG), jnp.ones((CFG.n_src,), bool))
    prims = list(_scoped_prims(
        jax.make_jaxpr(body)(carry, jnp.int32(5)).jaxpr))
    scopes = {c for _, stack in prims for c in stack.split("/")}
    assert GATHER_FREE_SCOPES <= scopes
    assert any(p == "gather" for p, _ in prims)
    bad = [stack for p, stack in prims if p == "gather"
           and GATHER_FREE_SCOPES & set(stack.split("/"))]
    assert not bad, f"{policy_name}: {len(bad)} gather(s): {bad}"


SMS_STAGES = {"sms.stage1", "sms.stage2", "sms.stage3"}


@pytest.mark.parametrize("policy_name", ["sms", "sms_dash"])
def test_sms_stage_lookups_build_no_gather(policy_name):
    """Every small-axis read of the SMS stages is a one-hot select and the
    front-run rescan an unrolled running AND: the per-cycle step has no
    gather and no cumprod under `sms.stage1`-`sms.stage3`, while the walk
    still sees the scatter-add that `engine.issue_channels` builds there."""
    prims = list(_scoped_prims(_step_jaxpr(policy_name).jaxpr))
    in_stage = [(p, stack) for p, stack in prims
                if SMS_STAGES & set(stack.split("/"))]
    assert SMS_STAGES <= {c for _, stack in in_stage
                          for c in stack.split("/")}
    assert any(p == "scatter-add" for p, _ in in_stage)
    bad = [(p, stack) for p, stack in in_stage if p in ("gather", "cumprod")]
    assert not bad, f"{policy_name}: {bad}"


def test_energy_accounting_adds_no_sorts_or_scatters():
    """repro.core.energy rides the per-cycle hot loop: enabling it must add
    zero sort/scatter/gather primitives to the step jaxpr (hot-loop rules
    1 + 3 — the counters are elementwise/one-hot-masked updates only)."""
    assert CFG.energy_enabled

    def counts(jx):
        out = {}
        for p, _ in _walk_prims(jx.jaxpr):
            fam = next((f for f in ("sort", "scatter", "gather")
                        if p.startswith(f)), None)
            if fam:
                out[fam] = out.get(fam, 0) + 1
        return out

    off_cfg = CFG.replace(energy_enabled=False)
    for name in ("frfcfs", "atlas", "sms"):
        on, off = counts(_step_jaxpr(name)), counts(_step_jaxpr(name, off_cfg))
        assert on == off, (
            f"{name}: energy accounting changed sort/scatter/gather "
            f"population: {off} -> {on}")


def test_qos_accounting_adds_no_sorts_or_scatters():
    """Same hot-loop contract for repro.core.qos: the latency histogram is
    a one-hot masked accumulation, so enabling it must add zero
    sort/scatter/gather primitives to the step jaxpr."""
    assert CFG.qos_enabled

    def counts(jx):
        out = {}
        for p, _ in _walk_prims(jx.jaxpr):
            fam = next((f for f in ("sort", "scatter", "gather")
                        if p.startswith(f)), None)
            if fam:
                out[fam] = out.get(fam, 0) + 1
        return out

    off_cfg = CFG.replace(qos_enabled=False)
    for name in ("frfcfs", "atlas", "sms"):
        on, off = counts(_step_jaxpr(name)), counts(_step_jaxpr(name, off_cfg))
        assert on == off, (
            f"{name}: QoS accounting changed sort/scatter/gather "
            f"population: {off} -> {on}")


def test_validate_off_adds_zero_primitives(monkeypatch):
    """The sanitizer is gated at TRACE time: with `validate_enabled=False`
    (the default) none of its counter functions may even be called during
    tracing, so the per-cycle jaxpr is untouched — zero primitives added,
    not merely zero sorts. Proven by poisoning every validate entry point
    and tracing both drivers."""
    assert not CFG.validate_enabled

    def boom(*a, **k):
        raise AssertionError("validate code reached with validate off")

    for fn in ("issue_counts", "tick_counts", "span_counts"):
        monkeypatch.setattr(validate, fn, boom)
    for name in ("frfcfs", "parbs", "sms"):
        cfg, pol, carry = sim._init(CFG, name)
        pool = _dummy_pool(cfg)
        active = jnp.ones((cfg.n_src,), bool)
        jax.make_jaxpr(policy_api.make_step(cfg, pol, pool, active))(
            carry, jnp.int32(5))
        body = policy_api.make_skip_step(cfg, pol, pool, active)
        jax.make_jaxpr(body)(carry, jnp.int32(5), jnp.int32(100))
    # non-vacuity: the same poison DOES fire once the sanitizer is on
    cfg, pol, carry = sim._init(CFG.replace(validate_enabled=True), "frfcfs")
    with pytest.raises(AssertionError, match="validate code reached"):
        jax.make_jaxpr(policy_api.make_step(
            cfg, pol, _dummy_pool(cfg),
            jnp.ones((cfg.n_src,), bool)))(carry, jnp.int32(5))


def _step_jaxpr_traced_knobs(policy_name, base_cfg=CFG):
    """Per-cycle step with the knob point as a TRACED argument (the batched
    design-grid path) instead of baked constants."""
    bound, pol, carry = sim._init(base_cfg, policy_name)
    pool = _dummy_pool(bound)
    active = jnp.ones((bound.n_src,), bool)
    base = bound.base

    def step(carry, t, kn):
        return policy_api.make_step(params.bind(base, kn), pol, pool,
                                    active)(carry, t)

    return jax.make_jaxpr(step)(carry, jnp.int32(5), Knobs.from_cfg(base))


def _prim_counts(jx):
    out = {}
    for p, _ in _walk_prims(jx.jaxpr):
        fam = next((f for f in ("sort", "scatter", "gather")
                    if p.startswith(f)), None)
        if fam:
            out[fam] = out.get(fam, 0) + 1
    return out


@pytest.mark.parametrize("policy_name", ["frfcfs", "atlas", "parbs", "sms"])
def test_knob_batching_adds_no_sorts_or_scatters(policy_name):
    """Lifting knobs from baked trace constants to traced arrays (the
    one-program design grid) must add ZERO sort/scatter/gather primitives
    to the per-cycle jaxpr — knob reads are elementwise operands, never
    indexing or ranking work."""
    baked = _prim_counts(_step_jaxpr(policy_name))
    traced = _prim_counts(_step_jaxpr_traced_knobs(policy_name))
    assert traced == baked, (
        f"{policy_name}: traced knobs changed sort/scatter/gather "
        f"population: {baked} -> {traced}")


@pytest.mark.parametrize("policy_name", ["atlas", "tcm"])
def test_traced_knobs_keep_sorts_cond_gated(policy_name):
    """The t-only boundary conds survive knob tracing: ranking sorts stay
    behind cond in the traced-knob jaxpr (period knobs are per-slice static,
    so the predicate stays unbatched)."""
    jx = _step_jaxpr_traced_knobs(policy_name)
    uncond = [p for p, in_cond in _walk_prims(jx.jaxpr)
              if p in SORT_PRIMS and not in_cond]
    assert not uncond, (
        f"{policy_name}: knob tracing un-gated {len(uncond)} sort op(s)")


def test_simspeed_bench_recorded_speedup_holds():
    """House gate on the recorded benchmark file: the sweep throughput
    captured in BENCH_simspeed.json must hold the hot-loop optimization win
    over the pre-optimization baseline. Refresh with `make bench-simspeed`
    after hot-loop signature changes — a refreshed "current" that falls
    under the gate means a real cycles/sec regression."""
    path = Path(__file__).parents[1] / "BENCH_simspeed.json"
    data = json.loads(path.read_text())
    ratio = data.get("sweep_speedup_vs_baseline_x")
    assert ratio is not None, \
        "BENCH_simspeed.json is missing the sweep speedup — run " \
        "`make bench-simspeed` to remeasure"
    assert ratio >= 2.0, (
        f"recorded sweep speedup {ratio:.2f}x < 2x baseline — the hot loop "
        f"regressed (or the BENCH file needs a remeasure on faster hardware)")


def test_scan_carry_has_no_pool_or_active():
    """The carry pytree holds only cycle-varying state."""
    for name in sim.ALL_POLICIES:
        _, _, (st, sched, dram) = sim._init(CFG, name)
        for tree in (st, sched, dram):
            assert "_pool" not in tree and "_active" not in tree, name
        assert not any(k.startswith("_") for k in st), \
            f"{name}: non-state key smuggled into the carry: {sorted(st)}"


# ---------------------------------------------------------------------------
# bit-identity re-check for the cond refactor (same protocol as
# test_policy_registry, focused on the three re-ranked policies)
# ---------------------------------------------------------------------------

GOLDEN = golden.load()


@pytest.mark.parametrize("policy_name", ["atlas", "parbs", "tcm"])
def test_cond_refactor_bit_identical(policy_name):
    # runs with the energy subsystem ON (CFG default): the goldens predate
    # it, so matching them on every non-energy key proves energy accounting
    # is purely additive to the scheduling decisions
    state = sim.simulate_debug(
        CFG, policy_name, golden.pool(CFG), np.ones(CFG.n_src, bool),
        n_cycles=golden.N_CYCLES)
    bad = golden.compare(policy_name, state, GOLDEN[policy_name])
    assert not bad, bad


@pytest.mark.parametrize("policy_name", ["atlas", "parbs", "tcm"])
def test_validate_on_bit_identical(policy_name):
    """Flipping the sanitizer ON is measurement-only: every golden digest
    still matches bit-for-bit (the counters never feed back into a
    scheduling decision), the only new dram key is the violation vector,
    and that vector is all zeros on a healthy run."""
    state = sim.simulate_debug(
        CFG.replace(validate_enabled=True), policy_name, golden.pool(CFG),
        np.ones(CFG.n_src, bool), n_cycles=golden.N_CYCLES)
    viol = np.asarray(state[2]["viol"])
    assert not viol.any(), validate.summarize(viol)
    bad = golden.compare(policy_name, state, GOLDEN[policy_name],
                         extra_dram=validate.STATE_KEYS)
    assert not bad, f"diverged under validate: {bad}"
