"""The `MemoryPolicy` registry: refactor equivalence + new-policy smoke.

The golden digests in `golden_policy_states.json` were captured from the
pre-registry string-dispatch code (`simulate_debug` final raw state, per
key, sha1 over dtype/shape/bytes). The ported policies must stay
bit-identical: src and dram state must match key-for-key in both
directions; scheduler state must match on every key that survived the port
(per-policy state was slimmed — e.g. frfcfs no longer carries ATLAS's
`attained` — so legacy-only keys are allowed to disappear, but shared keys
may not drift). The comparison itself is `repro.core.golden.compare`.
"""
import numpy as np
import pytest

from repro.core import energy, golden, policy
from repro.core import simulator as sim
from repro.core.params import SimConfig
from repro.serving.scheduler import SCHEDULERS as SERVING_SCHEDULERS

GOLDEN = golden.load()
CFG = golden.CFG
N_CYCLES = golden.N_CYCLES


@pytest.mark.parametrize("policy_name", sorted(GOLDEN))
def test_ported_policy_bit_identical(policy_name):
    # the goldens predate the energy subsystem; running them with it ON
    # proves the accounting is purely additive — every scheduling/service
    # key must still match bit-for-bit, and the only new dram keys allowed
    # are the energy counters themselves
    assert CFG.energy_enabled, "additivity check must run with energy on"
    state = sim.simulate_debug(
        CFG, policy_name, golden.pool(CFG), np.ones(CFG.n_src, bool),
        n_cycles=N_CYCLES)
    assert set(energy.STATE_KEYS) <= set(state[2]), \
        "energy counters missing — the additivity check would be vacuous"
    bad = golden.compare(policy_name, state, GOLDEN[policy_name])
    assert not bad, bad


# ---------------------------------------------------------------------------
# registry surface
# ---------------------------------------------------------------------------

def test_registry_enumerations():
    assert set(sim.POLICIES) == {"frfcfs", "atlas", "parbs", "tcm", "sms",
                                 "bliss", "squash_prio"}
    assert set(sim.ALL_POLICIES) == set(sim.POLICIES) | {"sms_dash"}
    for name in sim.ALL_POLICIES:
        pol = policy.get(name)
        assert pol.name == name
        for attr in ("configure", "init_state", "tick", "select"):
            assert callable(getattr(pol, attr)), (name, attr)
    assert policy.get("sms_dash").variant_of == "sms"


def test_registry_rejects_duplicates_and_unknowns():
    policy.names()          # force lazy built-in registration (order-proof)
    with pytest.raises(ValueError, match="duplicate"):
        policy.POLICY_REGISTRY.register("sms")(object())
    with pytest.raises(KeyError, match="unknown"):
        policy.get("nonexistent-policy")


def test_serving_registry_same_mechanism():
    """Serving schedulers enumerate through the same Registry class."""
    assert isinstance(SERVING_SCHEDULERS, policy.Registry)
    assert set(SERVING_SCHEDULERS.names()) >= {"fcfs", "locality", "sms",
                                               "sms_adaptive"}
    sched = SERVING_SCHEDULERS.get("sms")(4, seed=0)
    assert sched.n_clients == 4


# ---------------------------------------------------------------------------
# new policies: end-to-end smoke + no CPU starvation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy_name", ["bliss", "squash_prio"])
def test_new_policy_runs_and_never_starves_cpus(policy_name):
    cfg = SimConfig(n_cpu=4, n_channels=2, buf_entries=48, fifo_size=6,
                    dcs_size=4)
    rng = np.random.RandomState(7)
    S = cfg.n_src
    mpki = rng.uniform(15, 40, S).astype(np.float32)
    pool = {
        "mpki": mpki,
        "inst_per_miss": np.maximum(1000.0 / mpki, 1.0).astype(np.float32),
        "rbl": rng.uniform(0.3, 0.95, S).astype(np.float32),
        "blp": rng.randint(2, 7, S).astype(np.int32),
        "is_gpu": np.asarray([False] * cfg.n_cpu + [True]),
    }
    active = np.ones(S, bool)
    st_f, sched_f, dram_f = sim.simulate_debug(cfg, policy_name, pool,
                                               active, n_cycles=4_000)
    # conservation: emitted = completed + pending + in-flight + buffered
    in_struct = np.zeros(S, np.int64)
    for c in range(cfg.n_channels):
        for e in range(cfg.buf_entries):
            if sched_f["valid"][c, e]:
                in_struct[sched_f["src"][c, e]] += 1
    np.testing.assert_array_equal(
        st_f["emitted"].astype(np.int64),
        st_f["completed"] + st_f["pend_valid"] + dram_f["ring"].sum(0)
        + in_struct)
    # every CPU source makes real progress despite the GPU stream
    cpu_done = st_f["completed"][:cfg.n_cpu]
    assert (cpu_done > 0).all(), f"{policy_name} starved a CPU: {cpu_done}"
    assert (st_f["insts_done"][:cfg.n_cpu] > 0).all()


def test_bliss_blacklists_the_streaming_gpu():
    """An unopposed high-RBL GPU stream must trip the consecutive-serve
    blacklist (near-idle CPUs so serves are actually back-to-back)."""
    cfg = SimConfig(n_cpu=2, n_channels=1, buf_entries=32,
                    bliss_clear_interval=100_000)
    S = cfg.n_src
    pool = {
        "mpki": np.asarray([0.5, 0.5, 1000.0], np.float32),
        "inst_per_miss": np.asarray([2000.0, 2000.0, 1.0], np.float32),
        "rbl": np.asarray([0.3, 0.3, 0.95], np.float32),
        "blp": np.asarray([2, 2, 4], np.int32),
        "is_gpu": np.asarray([False, False, True]),
    }
    _, sched_f, _ = sim.simulate_debug(cfg, "bliss", pool,
                                       np.ones(S, bool), n_cycles=3_000)
    assert bool(sched_f["blacklist"][2]), "GPU never blacklisted"
