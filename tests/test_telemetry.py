"""Flight-recorder contract: the in-loop telemetry ring is free when off,
invisible when on, and exact under the variable-step driver.

Five layers:

  * OFF is absent — no `tl_*` state, and the per-cycle jaxpr traces with
    every telemetry entry point poisoned (so a leak raises at trace time),
    for both the ticked and the skipping driver. A twin test proves the
    poison actually fires when telemetry is ON, so the gate is not vacuous;
  * ON never changes a decision — with the recorder enabled, every
    non-telemetry final-state array is bit-identical to the telemetry-off
    run, for every registry policy, through the skipping driver;
  * driver-invariance — ticked and skipping runs produce bit-identical
    rings on every policy once the `steps` skip-meter channel is sliced
    off (`telemetry.N_INVARIANT`), and `steps` itself counts exactly the
    processed driver steps (the satellite skip-meter contract backing
    simspeed's ``cycles_per_s`` vs ``steps_per_s`` split);
  * stacked slices match solo runs — the ring rides the stacked carry;
  * the host-side views (`metrics.timeline_breakdown`) and the perf-trend
    ledger (`benchmarks.bench_trend`) hold their accounting identities.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, golden, telemetry
from repro.core import metrics as met
from repro.core import policy as policy_api
from repro.core import simulator as sim
from repro.core import workloads as wl
from repro.core.params import CLS_CPU, CLS_GPU, CLS_HWA, SimConfig

BASE = SimConfig(n_cpu=3, n_gpu=1, n_hwa=1, n_channels=2, buf_entries=24,
                 fifo_size=5, dcs_size=3)
# window * epoch = 1024 cycles retained >= every run length below: the ring
# holds the WHOLE run, so whole-run accounting identities are exact
CFG = BASE.replace(telemetry_enabled=True, telemetry_window=16,
                   telemetry_epoch=64)
N_CYCLES = 900
ALL_POLICIES = list(policy_api.names())


def _mix_pool():
    """(W=2, S=5) batch: row 0 busy 3-class mix, row 1 sparse/idle-heavy
    (spans form, so `skip_accrue` is actually exercised)."""
    mpki = np.array([[25, 40, 18, 1000, 1000],
                     [0.5, 1.0, 0.8, 1000, 1000]], np.float32)
    pool = {
        "mpki": mpki,
        "inst_per_miss": np.maximum(1000.0 / mpki, 1.0).astype(np.float32),
        "rbl": np.tile(np.array([.5, .4, .6, .9, .85], np.float32), (2, 1)),
        "blp": np.tile(np.array([3, 2, 4, 4, 2], np.int32), (2, 1)),
        "is_gpu": np.tile(np.array([0, 0, 0, 1, 0], bool), (2, 1)),
        "src_class": np.tile(np.array(
            [CLS_CPU] * 3 + [CLS_GPU, CLS_HWA], np.int32), (2, 1)),
        "dl_period": np.tile(np.array([0, 0, 0, 0, 400], np.int32), (2, 1)),
        "dl_reqs": np.tile(np.array([0, 0, 0, 0, 20], np.int32), (2, 1)),
        "dl_jitter": np.tile(np.array([0, 0, 0, 0, 10], np.int32), (2, 1)),
    }
    active = np.array([[1, 1, 1, 1, 1],
                       [1, 1, 0, 0, 1]], bool)
    return pool, active


def _row(pool, active, i):
    return {k: v[i] for k, v in pool.items()}, active[i]


def _stackable(cfg):
    return [n for n in ALL_POLICIES if policy_api.is_stackable(n, cfg)]


def _trace_both_drivers(cfg):
    """Trace the per-cycle step AND the skip body for frfcfs under cfg."""
    pool, active = _mix_pool()
    pool = sim.prepare_pool(_row(pool, active, 0)[0], (cfg.n_src,))
    cfg, pol, carry = sim._init(cfg, "frfcfs")
    active = jnp.ones((cfg.n_src,), bool)
    step = policy_api.make_step(cfg, pol, pool, active)
    jax.make_jaxpr(step)(carry, jnp.int32(5))
    skip = policy_api.make_skip_step(cfg, pol, pool, active)
    jax.make_jaxpr(lambda c, t: skip(c, t, jnp.int32(400)))(carry,
                                                            jnp.int32(5))


# ---------------------------------------------------------------------------
# (a) OFF is absent: no state, no primitives (poisoned entry points)
# ---------------------------------------------------------------------------

def test_off_no_state_and_zero_primitives(monkeypatch):
    """With the gate off there is no `tl_*` state, and tracing both driver
    bodies with every telemetry entry point replaced by a raiser succeeds:
    the off path contains no telemetry call at all."""
    assert not set(telemetry.STATE_KEYS) & set(engine.dram_state(BASE))

    def boom(*a, **k):
        raise AssertionError("telemetry entry point reached while off")
    for fn in ("snapshot", "tick_accrue", "skip_accrue"):
        monkeypatch.setattr(telemetry, fn, boom)
    _trace_both_drivers(BASE)                     # must not raise


def test_poison_fires_when_on(monkeypatch):
    """Non-vacuity twin: the same poison DOES fire when telemetry is on,
    so the zero-primitives test above is actually load-bearing."""
    def boom(*a, **k):
        raise AssertionError("telemetry entry point reached")
    monkeypatch.setattr(telemetry, "snapshot", boom)
    with pytest.raises(AssertionError, match="entry point reached"):
        _trace_both_drivers(CFG)


# ---------------------------------------------------------------------------
# (b) ON never changes a decision: off-vs-on final state bit-identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pol", ALL_POLICIES)
def test_on_is_measurement_only(pol):
    """Every non-telemetry array of the final raw state is bit-identical
    between telemetry-off and telemetry-on runs, through the SKIPPING
    driver on the sparse row (both `tick_accrue` and `skip_accrue` run)."""
    assert CFG.energy_enabled and CFG.qos_enabled
    pool, active = _mix_pool()
    pool1, act1 = _row(pool, active, 1)
    ref = sim.simulate_debug(BASE, pol, pool1, act1, N_CYCLES, skip=True)
    got = sim.simulate_debug(CFG, pol, pool1, act1, N_CYCLES, skip=True)
    for part, (r, g) in zip(("src", "sched", "dram"), zip(ref, got)):
        rd, gd = golden.digest(r), golden.digest(g)
        assert set(gd) - set(rd) <= set(telemetry.STATE_KEYS), \
            f"{pol} {part} grew unexpected keys: {set(gd) - set(rd)}"
        for k in rd:
            assert gd[k] == rd[k], f"{pol} {part}[{k}] diverged"
    assert "tl_ring" in got[2], "telemetry state missing — vacuous"


# ---------------------------------------------------------------------------
# (c) driver-invariance: ticked vs skipping rings, and the skip meter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pol", ALL_POLICIES)
def test_ring_bit_identical_ticked_vs_skipping(pol):
    """All channels before `steps` are driver-invariant (bit-identical
    between the ticked scan and the event-skipping while_loop); `steps`
    counts exactly the processed steps of whichever driver ran."""
    pool, active = _mix_pool()
    pool1, act1 = _row(pool, active, 1)
    ref = sim.simulate_debug(CFG, pol, pool1, act1, N_CYCLES, skip=False)
    got = sim.simulate_debug(CFG, pol, pool1, act1, N_CYCLES, skip=True)
    r_ring, g_ring = ref[2]["tl_ring"], got[2]["tl_ring"]
    np.testing.assert_array_equal(
        r_ring[:, :telemetry.N_INVARIANT],
        g_ring[:, :telemetry.N_INVARIANT],
        err_msg=f"{pol}: ring diverged between drivers")
    assert ref[2]["tl_epoch"] == got[2]["tl_epoch"]
    steps = telemetry.CH["steps"]
    assert r_ring[:, steps].sum() == N_CYCLES, pol
    assert g_ring[:, steps].sum() <= N_CYCLES, pol
    if pol in ("frfcfs", "atlas", "parbs"):       # known to skip here
        assert g_ring[:, steps].sum() < N_CYCLES, \
            f"{pol}: no spans formed — driver-invariance check is vacuous"


def test_accounting_identities_whole_run():
    """Window covers the run, so ring-channel sums equal whole-run totals:
    issues per class match the final per-source issue counters, row hits
    match the hit counter, `steps` matches the cycle count (ticked)."""
    pool, active = _mix_pool()
    pool0, act0 = _row(pool, active, 0)
    st_f, _, dram_f = sim.simulate_debug(CFG, "frfcfs", pool0, act0,
                                         N_CYCLES, skip=False)
    ring = dram_f["tl_ring"]
    cls = np.asarray(sim.prepare_pool(pool0, (CFG.n_src,))["src_class"])
    issued = np.asarray(dram_f["issued"])
    for c, name in ((CLS_CPU, "iss_cpu"), (CLS_GPU, "iss_gpu"),
                    (CLS_HWA, "iss_hwa")):
        assert ring[:, telemetry.CH[name]].sum() == issued[cls == c].sum()
    assert ring[:, telemetry.CH["row_hits"]].sum() == \
        np.asarray(dram_f["hits"]).sum()
    assert ring[:, telemetry.CH["steps"]].sum() == N_CYCLES


def test_skip_meter_agrees_with_sim_steps_on_bursty_archetypes():
    """Satellite contract behind simspeed's throughput split: the
    ``sim_steps`` metric (denominator of ``steps_per_s``, numerator of the
    reported skip ratio) equals the ring's `steps` channel — the driver's
    own processed-step counter — per workload, on the bursty archetype
    batch; the ticked driver pins both at exactly `n_cycles`."""
    cfg = CFG.replace(n_hwa=2)
    pool, active = wl.bursty_batch(cfg)
    n_cycles = 768                                # 12 epochs, window covers
    for skip in (False, True):
        m = sim.simulate(cfg, "frfcfs", pool, active, n_cycles=n_cycles,
                         warmup=0, skip=skip)
        steps_ch = np.asarray(m["telemetry"])[..., telemetry.CH["steps"]]
        per_wl = steps_ch.sum(axis=-1)
        np.testing.assert_array_equal(per_wl, np.asarray(m["sim_steps"]))
        ratio = 1.0 - np.asarray(m["sim_steps"]) / n_cycles
        if skip:
            assert ratio.max() > 0.2, \
                f"no archetype skipped ({ratio}) — the meter is untested"
        else:
            np.testing.assert_array_equal(ratio, np.zeros_like(ratio))


# ---------------------------------------------------------------------------
# (d) stacked slices match solo runs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("skip", [False, True], ids=["tick", "skip"])
def test_stacked_ring_matches_solo(skip):
    pool, active = _mix_pool()
    pool1, act1 = _row(pool, active, 1)
    fam = _stackable(CFG)
    out = sim.simulate_debug_stacked(CFG, fam, pool1, act1, N_CYCLES,
                                     skip=skip)
    for pol, (_, _, dram) in out.items():
        solo = sim.simulate_debug(CFG, pol, pool1, act1, N_CYCLES,
                                  skip=skip)[2]
        # the stacked skipping loop shares one step count across the
        # family, so `steps` is compared only on the ticked path
        n = telemetry.K if not skip else telemetry.N_INVARIANT
        np.testing.assert_array_equal(
            dram["tl_ring"][:, :n], solo["tl_ring"][:, :n],
            err_msg=f"{pol}: stacked ring slice != solo")
        assert dram["tl_epoch"] == solo["tl_epoch"], pol


# ---------------------------------------------------------------------------
# (e) host-side views and the perf-trend ledger
# ---------------------------------------------------------------------------

def test_timeline_breakdown_shapes_and_identities():
    pool, active = _mix_pool()
    total = 300 + 600
    m = sim.simulate(CFG, "frfcfs", pool, active, n_cycles=600, warmup=300,
                     skip=False)
    tb = met.timeline_breakdown(CFG, m, total_cycles=total)
    W = CFG.telemetry_window
    for k, v in tb.items():
        assert v.shape == (2, W), (k, v.shape)
    v = tb["valid"][0].astype(bool)
    assert v.any()
    ep = tb["epoch"][0][v]
    assert (np.diff(ep) == 1).all(), "epochs not contiguous ascending"
    assert (tb["occ_cpu"][..., v] >= 0).all()
    assert (tb["row_hit_rate"][..., v] <= 1.0 + 1e-6).all()
    # ticked run: every in-window cycle is a processed step
    np.testing.assert_allclose(tb["skip_ratio"][..., v], 0.0, atol=1e-6)


def test_bench_trend_check_and_ledger(tmp_path):
    from benchmarks import bench_trend

    def entry(cps, scale_cycles=1000):
        return {"ts": "t", "kind": "simspeed", "label": "x",
                "sweep": {"cycles_per_s": cps, "wall_s": 1.0},
                "scale": {"n_cycles": scale_cycles, "warmup": 10},
                "meta": {}}

    ledger = tmp_path / "ledger.jsonl"
    bench_trend.append_entry(entry(100.0), ledger)
    bench_trend.append_entry(entry(120.0), ledger)
    ledger.open("a").write("{corrupt\n")           # must be skipped, not fatal
    entries = bench_trend.load_ledger(ledger)
    assert len(entries) == 2
    ok, msg = bench_trend.check(entry(100.0), entries)       # -16.7% vs 120
    assert ok and "OK" in msg
    ok, msg = bench_trend.check(entry(90.0), entries)        # -25% vs 120
    assert not ok and "REGRESSION" in msg
    ok, msg = bench_trend.check(entry(50.0, scale_cycles=999), entries)
    assert ok and "nothing to compare" in msg      # scale mismatch: vacuous
    assert bench_trend.entry_from_summary({"no_sweep": 1}) is None
    e = bench_trend.entry_from_summary(
        {"sweep": {"cycles_per_s": 5.0, "wall_s": 2.0},
         "meta": {"sweep_scale": {"n_cycles": 7}, "jax": "x"}},
        kind="smoke", label="l")
    assert e["scale"] == {"n_cycles": 7} and e["kind"] == "smoke"


def test_committed_ledger_parses_and_passes():
    """The repo's seeded ledger must parse, and the committed
    BENCH_simspeed.json snapshot must hold its pace against it."""
    from benchmarks import bench_trend
    entries = bench_trend.load_ledger()
    assert entries, "BENCH_history.jsonl missing or empty"
    cand = bench_trend.candidate_from_bench()
    assert cand is not None
    ok, msg = bench_trend.check(cand, entries)
    assert ok, msg
