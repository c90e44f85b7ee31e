"""Simulation-throughput benchmark: the perf trajectory of the cycle sim.

Measures, per registered policy, steady-state simulation throughput
(simulated cycles × workloads per wall-second) and trace+compile time
(first call minus steady call); the cold-sweep wall-clock of the stackable
`CentralizedPolicy` family both stacked (one XLA program) and per-policy
("stacked_family" section); and the wall-clock of the fig4-equivalent
sweep (every registry policy, parity config, alone baselines included,
force-run through `common.run_sweep` into a throwaway cache dir). The
sweep also counts compiled XLA programs and asserts the one-program
property for the stacked family — `make bench-smoke` is the CI gate
against accidental de-stacking.

The "event_skip" section measures the variable-step driver: steady-state
wall-clock of the ticked scan vs the event-skipping while_loop on the
bursty archetype family (idle-dominated — the skip payoff) and on the
standard fig4-style mix (saturated — documents the per-step witness
overhead that keeps the skipping driver opt-in; the standard sweeps
tick), plus per-archetype skip ratios from the `sim_steps` metric. Throughput is reported on two
bases: ``cycles_per_s`` (simulated cycle-workloads per wall-second —
what cycle skipping improves) and ``steps_per_s`` (processed loop steps
per wall-second — per-step cost, which skipping must NOT regress), so
speedups are never conflated with skip ratio.

Results land in ``BENCH_simspeed.json`` at the repo root. The file keeps
two sections: ``baseline`` (the first measurement ever recorded — the
pre-optimization reference) and ``current`` (refreshed on every full-scale
run), plus the speedup ratio between them. Quick/smoke runs never touch
the file, so the baseline comparison stays apples-to-apples.

Usage:
    PYTHONPATH=src python -m benchmarks.simspeed            # full, writes
    PYTHONPATH=src python -m benchmarks.simspeed --smoke    # tiny, no write
"""
from __future__ import annotations

import argparse
import json
import platform
import time
from pathlib import Path
from typing import Dict, Sequence

import jax
import numpy as np

from benchmarks import common
from repro import compat, compile_cache
from repro.core import simulator as sim
from repro.core import workloads as wl

BENCH_PATH = Path(__file__).resolve().parents[1] / "BENCH_simspeed.json"

# canonical scales — change them only together with a fresh baseline
SWEEP_SCALE = dict(n_per_cat=15, n_cycles=16_000, warmup=2_000)
POLICY_SCALE = dict(n_per_cat=4, n_cycles=3_000, warmup=500)
# stacked-vs-per-policy family comparison: a COLD sweep of the stackable
# CentralizedPolicy family both ways. Deliberately compile-dominated (short
# cycle counts) — amortizing the per-policy trace+compile is exactly what
# the stacked path is for. Must not collide with SWEEP_SCALE's static args
# or the later all-policy sweep would find warm jit caches.
FAMILY_SCALE = dict(n_per_cat=4, n_cycles=2_000, warmup=500)
# ticked vs event-skipping driver comparison: steady-state (both modes
# compiled before timing), long cycle counts so the per-step loop cost
# dominates the dispatch overhead. Distinct static args again, so neither
# mode's program pollutes the sweep/family compile counts.
EVENT_SCALE = dict(n_per_cat=4, n_cycles=12_000, warmup=1_500)


def measure_per_policy(policies: Sequence[str], n_per_cat: int,
                       n_cycles: int, warmup: int) -> Dict[str, Dict]:
    """First call (trace+compile+run) vs steady call, per policy."""
    cfg = common.parity_config()
    wls = wl.make_workloads(cfg.n_cpu, n_per_cat=n_per_cat)
    pool, active = wl.pool_batch(cfg, wls)
    W = len(wls)
    out = {}
    for pol in policies:
        t0 = time.time()
        sim.simulate(cfg, pol, pool, active, n_cycles, warmup)
        t1 = time.time()
        m = sim.simulate(cfg, pol, pool, active, n_cycles, warmup)
        t2 = time.time()
        # `cycles_per_s` counts SIMULATED cycles: under the event-skipping
        # driver it credits jumped idle spans. `steps_per_s` counts cycles
        # the loop actually processed (scaled by the measured-window skip
        # ratio) — the per-step cost basis, immune to skip-ratio inflation.
        cps = (n_cycles + warmup) * W / (t2 - t1)
        ratio = 1.0 - float(np.mean(m["sim_steps"])) / n_cycles
        out[pol] = {
            "first_call_s": round(t1 - t0, 3),
            "steady_s": round(t2 - t1, 3),
            "compile_s": round((t1 - t0) - (t2 - t1), 3),
            "cycles_per_s": round(cps, 1),
            "steps_per_s": round(cps * (1.0 - ratio), 1),
            "skip_ratio": round(ratio, 3),
        }
    return out


def _xla_program_counts() -> Dict[str, int]:
    """Distinct compiled XLA programs per sim entry point (jit cache sizes)."""
    return {"stacked": compat.jit_cache_size(sim._sim_batch_stacked),
            "per_policy": compat.jit_cache_size(sim._sim_batch)}


def _cold_sweep(cfg, policies, wls, n_cycles, warmup, stacked, tag):
    """force-run `run_sweep` into a throwaway cache dir; returns wall_s."""
    with common.throwaway_cache(prefix="simspeed_"):
        t0 = time.time()
        common.run_sweep(cfg, policies, wls, n_cycles=n_cycles,
                         warmup=warmup, tag=tag, force=True, stacked=stacked)
        return time.time() - t0


def measure_sweep(policies: Sequence[str], n_per_cat: int, n_cycles: int,
                  warmup: int) -> Dict:
    """Fig4-equivalent sweep wall-clock: all policies, parity config,
    alone baselines included, cold caches (throwaway cache dir)."""
    cfg = common.parity_config()
    wls = wl.make_workloads(cfg.n_cpu, n_per_cat=n_per_cat)
    n_alone = len(wl.alone_batch(cfg)[2])
    before = _xla_program_counts()
    wall = _cold_sweep(cfg, policies, wls, n_cycles, warmup, stacked=True,
                       tag="simspeed")
    after = _xla_program_counts()
    cycw = (n_cycles + warmup) * (len(wls) + n_alone) * len(policies)
    return {
        "wall_s": round(wall, 2),
        "cycle_workloads": cycw,
        "cycles_per_s": round(cycw / wall, 1),
        "n_workloads": len(wls), "n_alone": n_alone,
        "n_cycles": n_cycles, "warmup": warmup,
        "policies": list(policies),
        "xla_programs": {k: after[k] - before[k] for k in after},
        "n_stackable": len(sim.stackable_names(cfg, policies)),
    }


def measure_nclass_smoke(n_cycles: int = 240, warmup: int = 60) -> Dict:
    """3-class mix (CPU+GPU+HWA): the stackable family must still compile
    as ONE XLA program with class ids + deadline streams in the pool.
    Tiny fixed scale — this is a compile-count gate, not a throughput
    measurement (the distinct config keeps its jit cache entry separate
    from the 2-class scales)."""
    cfg = common.parity_config(n_cpu=4, n_hwa=2)
    fam = sim.stackable_names(cfg)
    wls = wl.make_workloads(cfg.n_cpu, n_per_cat=1, n_hwa=cfg.n_hwa)
    pool, active = wl.pool_batch(cfg, wls)
    before = compat.jit_cache_size(sim._sim_batch_stacked)
    sim.simulate_stacked(cfg, fam, pool, active, n_cycles, warmup)
    after = compat.jit_cache_size(sim._sim_batch_stacked)
    return {"policies": list(fam), "n_hwa": cfg.n_hwa,
            "xla_programs": after - before}


def measure_knob_grid(n_cycles: int = 260, warmup: int = 60) -> Dict:
    """Design-grid smoke: the whole (policy x knob-variant) grid — every
    stackable policy crossed with value-knob variants plus a period-knob
    variant (per-slice static config) — must compile as ONE stacked XLA
    program (`sim.simulate_stacked_grid`). Tiny fixed scale: this is a
    compile-count gate for the batched-knob path (`make bench-dse`), not a
    throughput measurement."""
    cfg = common.parity_config()
    variants = [
        {},
        {"cpu_reserve": 0.25},
        {"cpu_reserve": 0.75, "energy_pd_idle": 16},
        # period-like knobs ride the per-slice static config, value-like
        # knobs the batched axis — one program must cover the mix
        {"atlas_epoch": 1500, "tcm_quantum": 800, "cpu_reserve": 0.625},
    ]
    fam = list(sim.stackable_names(cfg))
    slices = [(p, ov) for p in fam for ov in variants]
    wls = wl.make_workloads(cfg.n_cpu, n_per_cat=1)
    pool, active = wl.pool_batch(cfg, wls)
    before = compat.jit_cache_size(sim._sim_batch_stacked_grid)
    t0 = time.time()
    sim.simulate_stacked_grid(cfg, slices, pool, active, n_cycles, warmup)
    wall = time.time() - t0
    after = compat.jit_cache_size(sim._sim_batch_stacked_grid)
    t0 = time.time()
    sim.simulate_stacked_grid(cfg, slices, pool, active, n_cycles, warmup)
    steady = time.time() - t0
    return {"policies": fam, "n_variants": len(variants),
            "grid_points": len(slices), "wall_s": round(wall, 2),
            "steady_s": round(steady, 3),
            "compile_s": round(wall - steady, 3),
            "xla_programs": after - before}


def measure_stacked_family(n_per_cat: int, n_cycles: int, warmup: int
                           ) -> Dict:
    """Cold-sweep wall-clock for the stackable CentralizedPolicy family,
    stacked (one XLA program) vs per-policy (one program each)."""
    cfg = common.parity_config()
    fam = list(sim.stackable_names(cfg))
    wls = wl.make_workloads(cfg.n_cpu, n_per_cat=n_per_cat)
    out = {"policies": fam, "n_workloads": len(wls),
           "n_cycles": n_cycles, "warmup": warmup}
    for mode, stacked in (("stacked", True), ("per_policy", False)):
        out[f"{mode}_wall_s"] = round(
            _cold_sweep(cfg, fam, wls, n_cycles, warmup, stacked,
                        tag=f"simspeed_{mode}"), 2)
    out["stacked_speedup_x"] = round(
        out["per_policy_wall_s"] / out["stacked_wall_s"], 2)
    return out


def measure_event_skip(n_per_cat: int, n_cycles: int, warmup: int) -> Dict:
    """Ticked vs event-skipping driver, steady state, stacked family.

    Bursty archetype family: one stacked dispatch PER archetype (a batch
    would couple them — the shared while_loop runs until the least-skippy
    row finishes, capping the family win at the worst row's ratio), timed
    both ways after both modes are compiled; the family figure is the
    summed wall-clock. Standard fig4-style mix: one batched dispatch both
    ways — saturated traffic skips almost nothing, so this documents the
    witness overhead that makes the skipping driver OPT-IN
    (`sim.DEFAULT_SKIP`). Compile-count deltas per mode are recorded so
    the smoke gate can assert the skipping family still rides ONE stacked
    XLA program. Skip ratios come from the `sim_steps` metric
    (family-common: the stacked slices share one loop).
    """
    t_sec = time.time()
    out = {"n_cycles": n_cycles, "warmup": warmup}
    cfgb = common.parity_config(n_cpu=4, n_hwa=2)
    famb = list(sim.stackable_names(cfgb))
    bpool, bact = wl.bursty_batch(cfgb)
    rows = [({k: v[i:i + 1] for k, v in bpool.items()}, bact[i:i + 1])
            for i in range(len(wl.BURSTY_ARCHETYPES))]
    programs, compiles = {}, {}
    for mode, skip in (("ticked", False), ("skipping", True)):
        before = compat.jit_cache_size(sim._sim_batch_stacked)
        t0 = time.time()
        sim.simulate_stacked(cfgb, famb, *rows[0], n_cycles, warmup,
                             skip=skip)
        compiles[mode] = time.time() - t0   # first call: trace+compile+run
        programs[mode] = compat.jit_cache_size(sim._sim_batch_stacked) \
            - before
    per, tick_total, skip_total = {}, 0.0, 0.0
    for (p1, a1), name in zip(rows, wl.BURSTY_ARCHETYPES):
        t0 = time.time()
        sim.simulate_stacked(cfgb, famb, p1, a1, n_cycles, warmup,
                             skip=False)
        wt = time.time() - t0
        t0 = time.time()
        m = sim.simulate_stacked(cfgb, famb, p1, a1, n_cycles, warmup,
                                 skip=True)
        ws = time.time() - t0
        ratio = 1.0 - float(m[famb[0]]["sim_steps"][0]) / n_cycles
        per[name] = {"ticked_wall_s": round(wt, 3),
                     "skipping_wall_s": round(ws, 3),
                     "speedup_x": round(wt / max(ws, 1e-9), 2),
                     "skip_ratio": round(ratio, 3)}
        tick_total += wt
        skip_total += ws
    out["bursty"] = {
        "policies": famb,
        "archetypes": per,
        "skip_ratio": {a: per[a]["skip_ratio"] for a in per},
        "ticked_wall_s": round(tick_total, 3),
        "skipping_wall_s": round(skip_total, 3),
        "speedup_x": round(tick_total / max(skip_total, 1e-9), 2),
        "ticked_xla_programs": programs["ticked"],
        "skipping_xla_programs": programs["skipping"],
        # first-call wall (trace+compile+run) per mode; the steady walls
        # above subtract out as the compile-time share for the CI artifact
        "ticked_first_call_s": round(compiles["ticked"], 3),
        "skipping_first_call_s": round(compiles["skipping"], 3),
        "ticked_compile_s": round(
            compiles["ticked"] - per[wl.BURSTY_ARCHETYPES[0]]
            ["ticked_wall_s"], 3),
        "skipping_compile_s": round(
            compiles["skipping"] - per[wl.BURSTY_ARCHETYPES[0]]
            ["skipping_wall_s"], 3),
    }

    cfgs = common.parity_config()
    fams = list(sim.stackable_names(cfgs))
    wls = wl.make_workloads(cfgs.n_cpu, n_per_cat=n_per_cat)
    pool, active = wl.pool_batch(cfgs, wls)
    sres = {"n_workloads": len(wls)}
    for mode, skip in (("ticked", False), ("skipping", True)):
        before = compat.jit_cache_size(sim._sim_batch_stacked)
        t0 = time.time()
        sim.simulate_stacked(cfgs, fams, pool, active, n_cycles, warmup,
                             skip=skip)
        sres[f"{mode}_first_call_s"] = round(time.time() - t0, 3)
        sres[f"{mode}_xla_programs"] = \
            compat.jit_cache_size(sim._sim_batch_stacked) - before
        t0 = time.time()
        m = sim.simulate_stacked(cfgs, fams, pool, active, n_cycles,
                                 warmup, skip=skip)
        sres[f"{mode}_wall_s"] = round(time.time() - t0, 3)
        sres[f"{mode}_compile_s"] = round(
            sres[f"{mode}_first_call_s"] - sres[f"{mode}_wall_s"], 3)
    sres["speedup_x"] = round(sres["ticked_wall_s"]
                              / max(sres["skipping_wall_s"], 1e-9), 2)
    sres["mean_skip_ratio"] = round(
        1.0 - float(np.mean(m[fams[0]]["sim_steps"])) / n_cycles, 3)
    out["fig4_mix"] = sres
    out["wall_s"] = round(time.time() - t_sec, 2)
    return out


def measure_telemetry_gate(n_cycles: int = 280, warmup: int = 70) -> Dict:
    """Flight-recorder contract gates (ROADMAP "Telemetry contract").

    OFF must add ZERO primitives to the per-cycle jaxpr: telemetry's entry
    points are poisoned and both drivers re-traced — any residual call
    raises (the poisoned-entry pattern from tests/test_telemetry.py). ON
    must keep the stacked family at ONE XLA program (distinct static args
    keep its jit cache entry separate from every other scale here), and
    must strictly grow the step jaxpr (non-vacuity: the gate separates).
    """
    import jax
    import jax.numpy as jnp
    from repro.core import policy as policy_api
    from repro.core import telemetry

    cfg_off = common.parity_config(n_cpu=3)
    cfg_on = cfg_off.replace(telemetry_enabled=True, telemetry_window=8,
                             telemetry_epoch=64)

    def n_prims(cfg, poisoned):
        saved = {f: getattr(telemetry, f)
                 for f in ("snapshot", "tick_accrue", "skip_accrue")}

        def boom(*a, **k):
            raise AssertionError("telemetry entry point reached while off")
        try:
            if poisoned:
                for f in saved:
                    setattr(telemetry, f, boom)
            rcfg, pol, carry = sim._init(cfg, "frfcfs")
            pool = sim.prepare_pool(
                {"mpki": np.ones((rcfg.n_src,), np.float32),
                 "inst_per_miss": np.full((rcfg.n_src,), 100.0, np.float32),
                 "rbl": np.full((rcfg.n_src,), 0.5, np.float32),
                 "blp": np.ones((rcfg.n_src,), np.int32),
                 "is_gpu": np.zeros((rcfg.n_src,), bool)},
                (rcfg.n_src,))
            active = jnp.ones((rcfg.n_src,), bool)
            step = policy_api.make_step(rcfg, pol, pool, active)
            jx = jax.make_jaxpr(step)(carry, jnp.int32(5))
            skip = policy_api.make_skip_step(rcfg, pol, pool, active)
            jax.make_jaxpr(lambda c, t: skip(c, t, jnp.int32(400))
                           )(carry, jnp.int32(5))
            return sum(1 for _ in compat.walk_primitives(jx.jaxpr))
        finally:
            for f, fn in saved.items():
                setattr(telemetry, f, fn)

    off_prims = n_prims(cfg_off, poisoned=True)   # raises if gate leaks
    on_prims = n_prims(cfg_on, poisoned=False)
    fam = list(sim.stackable_names(cfg_on))
    wls = wl.make_workloads(cfg_on.n_cpu, n_per_cat=1)
    pool, active = wl.pool_batch(cfg_on, wls)
    before = compat.jit_cache_size(sim._sim_batch_stacked)
    sim.simulate_stacked(cfg_on, fam, pool, active, n_cycles, warmup)
    after = compat.jit_cache_size(sim._sim_batch_stacked)
    return {
        "off_zero_prims": True,                   # poisoned trace survived
        "step_prims_off": off_prims,
        "step_prims_on": on_prims,
        "on_grows_jaxpr": on_prims > off_prims,
        "xla_programs": after - before,
        "policies": fam,
    }


def main(sweep_scale: Dict = None, policy_scale: Dict = None,
         family_scale: Dict = None, event_scale: Dict = None,
         write: bool = True, summary_out: str = None) -> Dict:
    compile_cache.enable()
    sweep_scale = sweep_scale or SWEEP_SCALE
    policy_scale = policy_scale or POLICY_SCALE
    family_scale = family_scale or FAMILY_SCALE
    event_scale = event_scale or EVENT_SCALE
    policies = list(sim.ALL_POLICIES)
    # the energy subsystem rides the hot loop by default; the compile-count
    # and trace-size gates below are only meaningful if they cover it
    assert common.parity_config().energy_enabled, \
        "bench gate must measure the energy-accounting hot loop"

    t0 = time.time()
    per_policy = measure_per_policy(policies, **policy_scale)
    for pol, r in per_policy.items():
        print(f"  {pol}: steady={r['steady_s']}s compile={r['compile_s']}s "
              f"cycles_per_s={r['cycles_per_s']:,.0f}")
    family = measure_stacked_family(**family_scale)
    print(f"  stacked family ({len(family['policies'])} policies, cold): "
          f"{family['stacked_wall_s']}s stacked vs "
          f"{family['per_policy_wall_s']}s per-policy "
          f"({family['stacked_speedup_x']}x)")
    sweep = measure_sweep(policies, **sweep_scale)
    print(f"  sweep: {sweep['wall_s']}s -> {sweep['cycles_per_s']:,.0f} "
          f"cycle-workloads/s; xla_programs={sweep['xla_programs']}")
    nclass = measure_nclass_smoke()
    print(f"  3-class smoke ({len(nclass['policies'])} policies, "
          f"{nclass['n_hwa']} HWAs): xla_programs={nclass['xla_programs']}")
    knob_grid = measure_knob_grid()
    print(f"  knob grid ({knob_grid['grid_points']} points = "
          f"{len(knob_grid['policies'])} policies x "
          f"{knob_grid['n_variants']} variants): "
          f"xla_programs={knob_grid['xla_programs']} "
          f"in {knob_grid['wall_s']}s")
    event = measure_event_skip(**event_scale)
    print(f"  event skip: bursty {event['bursty']['ticked_wall_s']}s ticked"
          f" vs {event['bursty']['skipping_wall_s']}s skipping "
          f"({event['bursty']['speedup_x']}x, "
          f"ratios={event['bursty']['skip_ratio']}); fig4 mix "
          f"{event['fig4_mix']['speedup_x']}x at mean skip ratio "
          f"{event['fig4_mix']['mean_skip_ratio']}")
    tel = measure_telemetry_gate()
    print(f"  telemetry: off adds 0 prims (poisoned trace ok, "
          f"{tel['step_prims_off']} prims), on grows jaxpr to "
          f"{tel['step_prims_on']} and stays {tel['xla_programs']} "
          f"stacked program")

    current = {
        "meta": {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "sweep_scale": dict(sweep_scale),
            "policy_scale": dict(policy_scale),
            "family_scale": dict(family_scale),
            "event_scale": dict(event_scale),
        },
        "per_policy": per_policy,
        "stacked_family": family,
        "sweep": sweep,
        "nclass_smoke": nclass,
        "knob_grid": knob_grid,
        "event_skip": event,
        "telemetry_gate": tel,
    }
    # CI gate (bench-smoke): the whole stackable family must ride ONE XLA
    # program through the sweep — with energy accounting enabled (asserted
    # above) — and only the SMS-style protocols may fall back to per-policy
    # compiles. Catches accidental de-stacking, including by energy state.
    # The summary artifact is written BEFORE the asserts, with the measured
    # gate outcomes, so a failed gate is diagnosable from the artifact.
    n_fallback = len(policies) - sweep["n_stackable"]
    gates = {
        "energy_enabled": True,                    # asserted at entry
        "stacked_one_program": sweep["xla_programs"]["stacked"] == 1,
        "per_policy_fallbacks_ok":
            sweep["xla_programs"]["per_policy"] == n_fallback,
        "expected_fallbacks": n_fallback,
        "nclass_one_program": nclass["xla_programs"] == 1,
        # the batched-knob design grid (bench-dse) is ONE stacked program
        "dse_one_program": knob_grid["xla_programs"] == 1
            and knob_grid["grid_points"] >= 24,
        # the event-skipping driver is a second while_loop body, not a
        # second program per policy: one stacked compile per batch shape
        "skip_one_program":
            event["bursty"]["skipping_xla_programs"] == 1
            and event["fig4_mix"]["skipping_xla_programs"] == 1,
        # idle_cpu is the archetype whose spans stay long even at smoke
        # cycle counts; a collapse here means witnesses got conservative
        "bursty_min_skip_ratio_ok":
            event["bursty"]["skip_ratio"]["idle_cpu"] >= 0.5,
        # flight recorder: OFF must add zero primitives to the hot loop
        # (poisoned entry points + an unchanged trace prove it), ON must
        # not de-stack the family — and must actually change the jaxpr,
        # or the zero-prims gate would be vacuous
        "telemetry_off_zero_prims":
            tel["off_zero_prims"] and tel["on_grows_jaxpr"],
        "telemetry_one_program": tel["xla_programs"] == 1,
    }
    if summary_out:
        Path(summary_out).write_text(json.dumps(
            {"current": current, "gates": gates}, indent=1) + "\n")
    assert gates["stacked_one_program"], \
        f"centralized family de-stacked: {sweep['xla_programs']}"
    assert gates["per_policy_fallbacks_ok"], \
        f"expected {n_fallback} per-policy programs: {sweep['xla_programs']}"
    assert gates["nclass_one_program"], \
        f"3-class mix de-stacked the family: {nclass['xla_programs']} programs"
    assert gates["dse_one_program"], \
        f"knob grid de-stacked: {knob_grid['grid_points']} points compiled " \
        f"{knob_grid['xla_programs']} stacked programs, expected 1"
    assert gates["skip_one_program"], \
        "skipping driver de-stacked the family: " \
        f"bursty={event['bursty']['skipping_xla_programs']} " \
        f"fig4={event['fig4_mix']['skipping_xla_programs']} programs"
    assert gates["bursty_min_skip_ratio_ok"], \
        f"idle_cpu skip ratio collapsed: {event['bursty']['skip_ratio']}"
    assert gates["telemetry_off_zero_prims"], \
        f"telemetry gate leaked into the off path: {tel}"
    assert gates["telemetry_one_program"], \
        f"telemetry de-stacked the family: {tel['xla_programs']} programs"
    data = {}
    if BENCH_PATH.exists():
        data = json.loads(BENCH_PATH.read_text())
    if "baseline" not in data:
        data["baseline"] = current
    data["current"] = current
    cur = current["sweep"]["cycles_per_s"]
    # the baseline ratio is only meaningful at the baseline's own scale;
    # never leave a stale ratio next to a differently-scaled "current"
    same_scale = (data["baseline"]["meta"]["sweep_scale"]
                  == current["meta"]["sweep_scale"])
    if same_scale:
        base = data["baseline"]["sweep"]["cycles_per_s"]
        data["sweep_speedup_vs_baseline_x"] = round(cur / base, 2)
    else:
        data.pop("sweep_speedup_vs_baseline_x", None)
    speedup = data.get("sweep_speedup_vs_baseline_x", "n/a")
    if write:
        BENCH_PATH.write_text(json.dumps(data, indent=1) + "\n")

    us = (time.time() - t0) * 1e6 / max(len(policies), 1)
    common.emit("simspeed", us,
                f"sweep_cycles_per_s={cur:.0f};"
                f"speedup_vs_baseline_x={speedup};written={write}")
    return data


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny cycle counts, no BENCH file write — catches "
                    "trace-size/compile-time regressions in CI")
    ap.add_argument("--summary-out", default=None,
                    help="write a JSON run summary to this path (CI artifact)")
    args = ap.parse_args()
    if args.smoke:
        # family/sweep smoke scales must differ in static args, or the
        # sweep's compile-count assertion would find warm jit caches
        main(sweep_scale=dict(n_per_cat=1, n_cycles=300, warmup=100),
             policy_scale=dict(n_per_cat=1, n_cycles=200, warmup=50),
             family_scale=dict(n_per_cat=1, n_cycles=250, warmup=50),
             event_scale=dict(n_per_cat=1, n_cycles=400, warmup=80),
             write=False, summary_out=args.summary_out)
    else:
        main(summary_out=args.summary_out)
