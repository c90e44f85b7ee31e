"""Event-driven cycle skipping: the variable-step driver contract.

The skipping driver (`_run_cycles` with a `make_skip_step` body) replaces
the fixed lax.scan with a while_loop that processes cycle t and then jumps
straight to the earliest witnessed next event. Contract, checked here:

  * SEMANTIC INVISIBILITY — ticked and skipping runs are BIT-identical:
    every metric (energy + QoS on) and every raw final-state array, for
    every registered policy, on both a busy 3-class mix and a sparse
    idle-heavy mix. `sim_steps` is the one intentional exception (it IS
    the skip measurement);
  * skipped spans charge background energy exactly: the integer
    standby/power-down counters partition every channel-cycle with no
    drift, and match the ticked accrual bit-for-bit;
  * the skip never jumps past an HWA frame release or a t-only boundary
    edge (epoch ranks, quantum shuffles, probabilistic redraws) — frame
    releases land cycle-exact and the boundary-policy states stay
    bit-identical on idle spans, where a late jump would starve the edge;
  * the PAR-BS amortized-rank residue fix: the stacked slice still
    matches the pre-refactor per-policy golden digests, running THROUGH
    the skipping driver.
"""
import numpy as np
import pytest

from repro.core import golden
from repro.core import policy as policy_api
from repro.core import simulator as sim
from repro.core.params import CLS_CPU, CLS_GPU, CLS_HWA, SimConfig

CFG = SimConfig(n_cpu=3, n_gpu=1, n_hwa=1, n_channels=2, buf_entries=24,
                fifo_size=5, dcs_size=3)
N_CYCLES = 1_500
ALL_POLICIES = list(policy_api.names())


def _mix_pool():
    """(W=2, S=5) batch: row 0 busy 3-class mix, row 1 sparse/idle-heavy
    (low-mpki CPUs + a slow frame HWA; GPU masked off via `active`)."""
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


# ---------------------------------------------------------------------------
# (a) ticked vs skipping bit-identity, every policy, energy + QoS on
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pol", ALL_POLICIES)
def test_metrics_bit_identical_and_skip_nonvacuous(pol):
    assert CFG.energy_enabled and CFG.qos_enabled
    pool, active = _mix_pool()
    ref = sim.simulate(CFG, pol, pool, active, N_CYCLES, 300, skip=False)
    got = sim.simulate(CFG, pol, pool, active, N_CYCLES, 300, skip=True)
    assert set(ref) == set(got)
    for k in ref:
        if k == "sim_steps":
            continue
        np.testing.assert_array_equal(ref[k], got[k], err_msg=f"{pol}:{k}")
    # ticked driver processes every cycle; the skipping one must actually
    # skip on the idle-heavy row or the whole apparatus is vacuous
    assert (ref["sim_steps"] == N_CYCLES).all(), pol
    assert got["sim_steps"][1] < 0.95 * N_CYCLES, \
        f"{pol}: no skip on the idle-heavy mix ({got['sim_steps'][1]})"


@pytest.mark.parametrize("pol", ["frfcfs", "atlas", "parbs", "squash_prio",
                                 "sms"])
def test_final_raw_state_bit_identical(pol):
    """Full-state digest equality on the sparse mix: covers per-cycle
    boundary machinery (atlas epoch ranks, squash urgency flips + redraws,
    SMS batch ageing) landing on exactly the right edges mid-idle-span."""
    pool, active = _mix_pool()
    pool1, act1 = _row(pool, active, 1)
    ref = sim.simulate_debug(CFG, pol, pool1, act1, N_CYCLES, skip=False)
    got = sim.simulate_debug(CFG, pol, pool1, act1, N_CYCLES, skip=True)
    for part, (r, s) in zip(("src", "sched", "dram"), zip(ref, got)):
        rd, sd = golden.digest(r), golden.digest(s)
        assert set(sd) == set(rd), f"{pol} {part} keys drifted"
        for k in rd:
            assert sd[k] == rd[k], f"{pol} {part}[{k}] diverged"


# ---------------------------------------------------------------------------
# (b) skipped spans charge standby/power-down energy exactly
# ---------------------------------------------------------------------------

def test_skipped_span_background_accrual_exact():
    pool, active = _mix_pool()
    pool1, _ = _row(pool, active, 1)
    lone = np.zeros(CFG.n_src, bool)
    lone[0] = True                       # one sparse CPU: long idle spans
    _, _, d_ref = sim.simulate_debug(CFG, "frfcfs", pool1, lone, N_CYCLES,
                                     skip=False)
    _, _, d_got = sim.simulate_debug(CFG, "frfcfs", pool1, lone, N_CYCLES,
                                     skip=True)
    # integer counters: exact partition of every channel-cycle, and the
    # one-multiply span accrual reproduces the per-cycle walk bit-for-bit
    for d in (d_ref, d_got):
        assert int(d["sb_cycles"].sum() + d["pd_cycles"].sum()) \
            == CFG.n_channels * N_CYCLES
    for k in ("sb_cycles", "pd_cycles", "pd_down", "e_wake", "busy_until"):
        np.testing.assert_array_equal(d_ref[k], d_got[k], err_msg=k)
    assert int(d_got["pd_cycles"].sum()) > 0, "span never entered power-down"
    # non-vacuity: this scenario must actually exercise long skips
    m = sim.simulate(CFG, "frfcfs", {k: v[None] for k, v in pool1.items()},
                     lone[None], N_CYCLES, 0, skip=True)
    assert m["sim_steps"][0] < 0.3 * N_CYCLES


# ---------------------------------------------------------------------------
# (c) skips stop at HWA frame releases and t-only boundary edges
# ---------------------------------------------------------------------------

def test_skip_stops_at_hwa_frame_releases():
    """`frames_released` counts deadline-frame starts cycle-exactly; a jump
    past a release would undercount it (and desync every deadline metric).
    Run mostly-idle so releases are the dominant wake-up reason."""
    pool, active = _mix_pool()
    pool1, act1 = _row(pool, active, 1)
    st_ref, _, _ = sim.simulate_debug(CFG, "frfcfs", pool1, act1, N_CYCLES,
                                      skip=False)
    st_got, _, _ = sim.simulate_debug(CFG, "frfcfs", pool1, act1, N_CYCLES,
                                      skip=True)
    np.testing.assert_array_equal(st_ref["frames_released"],
                                  st_got["frames_released"])
    hwa = CFG.n_src - 1
    assert int(st_got["frames_released"][hwa]) == (N_CYCLES - 1) // 400, \
        "skipping run missed a frame release"


# ---------------------------------------------------------------------------
# (d) PAR-BS residue fix: stacked slice vs pre-refactor golden, skipping
# ---------------------------------------------------------------------------

def test_parbs_stacked_slice_matches_golden_through_skip_driver():
    """The amortized-rank reformulation (no per-cycle sort, no batched
    cond residue) + the skipping driver, against the digests captured
    before either existed: the batch machinery is bit-preserved."""
    fam = sim.stackable_names(golden.CFG)
    out = sim.simulate_debug_stacked(golden.CFG, fam, golden.pool(),
                                     np.ones(golden.CFG.n_src, bool),
                                     n_cycles=golden.N_CYCLES, skip=True)
    bad = golden.compare("parbs", out["parbs"], golden.load()["parbs"])
    assert not bad, bad
