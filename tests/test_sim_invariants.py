"""Simulator invariants (unit + hypothesis property tests).

Conservation: every emitted request is exactly one of {completed, waiting in
an MC structure, pending at the core}. Structural bounds: FIFO lengths within
capacity, non-negative stats. Physical bounds: data-bus occupancy can never
exceed 1 burst per t_burst cycles per channel.
"""
import jax
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="dev dep; pip install -r "
                    "requirements-dev.txt")
from hypothesis import given, settings, strategies as st

from repro.core import simulator as sim
from repro.core.params import SimConfig

CFG = SimConfig(n_cpu=3, n_channels=2, buf_entries=24, fifo_size=5,
                dcs_size=3)


def _pool(rng: np.random.RandomState, cfg: SimConfig, with_deadline=False):
    S = cfg.n_src
    mpki = rng.uniform(2, 40, S).astype(np.float32)
    pool = {
        "mpki": mpki,
        "inst_per_miss": np.maximum(1000.0 / mpki, 1.0).astype(np.float32),
        "rbl": rng.uniform(0.1, 0.95, S).astype(np.float32),
        "blp": rng.randint(1, 7, S).astype(np.int32),
        "is_gpu": np.asarray([False] * cfg.n_cpu + [True]),
        "dl_period": np.zeros(S, np.int32),
        "dl_reqs": np.zeros(S, np.int32),
    }
    if with_deadline and cfg.n_cpu >= 2:
        # turn one "cpu" slot into a frame-deadline accelerator
        pool["dl_period"][0] = int(rng.randint(300, 900))
        pool["dl_reqs"][0] = int(rng.randint(5, 40))
    return pool


def _conservation(cfg, st_f, sched_f, dram_f, policy):
    emitted = st_f["emitted"].astype(np.int64)
    completed = st_f["completed"].astype(np.int64)
    pending = st_f["pend_valid"].astype(np.int64)
    in_ring = dram_f["ring"].sum(0).astype(np.int64)
    S = cfg.n_src
    in_struct = np.zeros(S, np.int64)
    if policy.startswith("sms"):
        for s in range(S):
            in_struct[s] += sched_f["f_len"][:, s].sum()
        d_src, d_len, d_head = (sched_f["d_src"], sched_f["d_len"],
                                sched_f["d_head"])
        C, B, D = d_src.shape
        for c in range(C):
            for b in range(B):
                for i in range(d_len[c, b]):
                    in_struct[d_src[c, b, (d_head[c, b] + i) % D]] += 1
    else:
        for c in range(cfg.n_channels):
            for e in range(cfg.buf_entries):
                if sched_f["valid"][c, e]:
                    in_struct[sched_f["src"][c, e]] += 1
    lhs = emitted
    rhs = completed + pending + in_ring + in_struct
    np.testing.assert_array_equal(lhs, rhs)


@pytest.mark.parametrize("policy", sim.POLICIES)
def test_request_conservation(policy):
    rng = np.random.RandomState(0)
    pool = _pool(rng, CFG)
    active = np.ones(CFG.n_src, bool)
    st_f, sched_f, dram_f = sim.simulate_debug(CFG, policy, pool, active,
                                               n_cycles=3_000)
    _conservation(CFG, st_f, sched_f, dram_f, policy)
    assert (st_f["outstanding"] >= 0).all()
    assert (st_f["outstanding"] ==
            st_f["emitted"] - st_f["completed"]).all()


@pytest.mark.parametrize("policy", ["sms", "frfcfs"])
def test_bus_capacity_bound(policy):
    """Completions can't exceed the data-bus capacity (1 / t_burst / chan)."""
    rng = np.random.RandomState(1)
    pool = _pool(rng, CFG)
    active = np.ones(CFG.n_src, bool)
    n_cycles = 4_000
    st_f, _, dram_f = sim.simulate_debug(CFG, policy, pool, active, n_cycles)
    total = int(st_f["completed"].sum())
    cap = n_cycles * CFG.n_channels / CFG.timing.t_burst
    assert total <= cap * 1.01


def test_sms_structure_bounds():
    rng = np.random.RandomState(2)
    pool = _pool(rng, CFG)
    active = np.ones(CFG.n_src, bool)
    _, sms_f, _ = sim.simulate_debug(CFG, "sms", pool, active, 3_000)
    assert (sms_f["f_len"] >= 0).all() and \
        (sms_f["f_len"] <= CFG.fifo_size).all()
    assert (sms_f["d_len"] >= 0).all() and \
        (sms_f["d_len"] <= CFG.dcs_size).all()
    assert (sms_f["drain_left"] >= 0).all()


@settings(deadline=None, max_examples=6)
@given(st.integers(0, 10_000),
       st.sampled_from(["sms", "sms_dash", "tcm", "frfcfs"]))
def test_conservation_property(seed, policy):
    """Hypothesis: conservation holds for random source parameterizations."""
    rng = np.random.RandomState(seed)
    cfg = SimConfig(n_cpu=int(rng.randint(2, 5)), n_channels=1,
                    buf_entries=16, fifo_size=4, dcs_size=2)
    pool = _pool(rng, cfg, with_deadline=(policy == "sms_dash"))
    active = rng.rand(cfg.n_src) < 0.8
    active[-1] = True
    active[0] = True
    st_f, sched_f, dram_f = sim.simulate_debug(cfg, policy, pool, active,
                                               n_cycles=1_500)
    _conservation(cfg, st_f, sched_f, dram_f, policy)


def test_inactive_sources_stay_silent():
    rng = np.random.RandomState(3)
    pool = _pool(rng, CFG)
    active = np.zeros(CFG.n_src, bool)
    active[0] = True
    st_f, _, _ = sim.simulate_debug(CFG, "sms", pool, active, 2_000)
    assert st_f["emitted"][1:].sum() == 0
    assert st_f["emitted"][0] > 0


@pytest.mark.parametrize("kind", ["counts", "wide", "per_cycle"])
def test_div_rn_is_ieee_division(kind):
    """`div_rn` (the metric ratios) rounds exactly as IEEE f32 division."""
    rng = np.random.default_rng(7)
    n = 100_000
    if kind == "counts":
        a = rng.integers(0, 2**31 - 128, n).astype(np.float32)
        b = rng.integers(1, 2**20, n).astype(np.float32)
    elif kind == "wide":
        a = np.exp(rng.uniform(-40, 40, n)).astype(np.float32)
        b = np.exp(rng.uniform(-40, 40, n)).astype(np.float32)
    else:
        a = rng.uniform(0, 1e6, n).astype(np.float32)
        b = np.full(n, 16_000, np.float32)
    a[:3] = [0.0, 1.0, 3.0]
    got = np.asarray(jax.jit(sim.div_rn)(a, b))
    want = a / b
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_rbl_measured_tracks_generator():
    """High-RBL source measured row-hit rate >> low-RBL source (alone)."""
    from repro.core import workloads as wl
    cfg = SimConfig(n_cpu=1, n_channels=1, buf_entries=16, fifo_size=8,
                    dcs_size=4)
    for rbl, lo, hi in ((0.9, 0.6, 1.0), (0.2, 0.0, 0.45)):
        pool = {
            "mpki": np.asarray([40.0, 40.0], np.float32),
            "inst_per_miss": np.asarray([25.0, 25.0], np.float32),
            "rbl": np.asarray([rbl, rbl], np.float32),
            "blp": np.asarray([2, 2], np.int32),
            "is_gpu": np.asarray([False, True]),
        }
        m = sim.simulate(cfg, "frfcfs", {k: v[None] for k, v in pool.items()},
                         np.asarray([[True, False]]), 6_000, 500)
        measured = float(m["rbl"][0, 0])
        assert lo <= measured <= hi, f"rbl={rbl} measured={measured}"
