"""Stacked cross-policy execution: equivalence + hot-loop invariants.

The stacked path (schedulers.make_stacked_step) runs the whole stackable
`CentralizedPolicy` family as one scan over states stacked on a leading
policy axis. Contract, checked here:

  * every policy's slice is BIT-identical to its standalone run — pinned
    against the same golden digests `test_policy_registry` uses, and
    cross-checked against the vmapped `simulate` path metric-for-metric;
  * the stacked step keeps hot-loop rule 1: sort primitives appear only
    inside cond branches (each policy's t-only boundary predicate stays a
    genuine scalar cond on its own slice — the reason dispatch is per
    policy index rather than a batched `lax.switch`, which would dissolve
    the nested conds under vmap);
  * the union state schema refuses shape/dtype collisions instead of
    silently mis-padding;
  * stackability is an explicit opt-in: SMS-style protocols and configured
    variants (sms_dash) stay on the per-policy path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import golden
from repro.core import policy as policy_api
from repro.core import schedulers
from repro.core import simulator as sim

CFG = golden.CFG
SORT_PRIMS = {"sort"}

GOLDEN = golden.load()

FAMILY = sim.stackable_names(CFG)


# ---------------------------------------------------------------------------
# bit-identity: stacked slices vs the pre-stacking golden digests
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stacked_final_states():
    """One stacked run of the whole family at the golden config."""
    return sim.simulate_debug_stacked(
        CFG, FAMILY, golden.pool(CFG), np.ones(CFG.n_src, bool),
        n_cycles=golden.N_CYCLES)


@pytest.mark.parametrize("policy_name",
                         [n for n in FAMILY if n in GOLDEN])
def test_stacked_slice_bit_identical_to_golden(policy_name,
                                               stacked_final_states):
    bad = golden.compare(policy_name, stacked_final_states[policy_name],
                         GOLDEN[policy_name])
    assert not bad, bad


@pytest.mark.parametrize("policy_name",
                         [n for n in FAMILY if n not in GOLDEN])
def test_stacked_slice_bit_identical_to_debug(policy_name,
                                              stacked_final_states):
    """Policies younger than the golden capture (bliss, squash_prio):
    compare the stacked slice against a fresh standalone run instead."""
    ref = sim.simulate_debug(CFG, policy_name, golden.pool(CFG),
                             np.ones(CFG.n_src, bool),
                             n_cycles=golden.N_CYCLES)
    got = stacked_final_states[policy_name]
    for part, (r, s) in zip(("src", "sched", "dram"), zip(ref, got)):
        rd, sd = golden.digest(r), golden.digest(s)
        assert set(sd) == set(rd), f"{policy_name} {part} keys drifted"
        for k in rd:
            assert sd[k] == rd[k], f"{policy_name} {part}[{k}] diverged"


def test_stacked_metrics_match_per_policy_simulate():
    """The jitted (workload-vmapped) stacked path == per-policy simulate."""
    rng = np.random.RandomState(3)
    W, S = 2, CFG.n_src
    mpki = rng.uniform(2, 40, (W, S)).astype(np.float32)
    pool = {
        "mpki": mpki,
        "inst_per_miss": np.maximum(1000.0 / mpki, 1.0).astype(np.float32),
        "rbl": rng.uniform(0.1, 0.95, (W, S)).astype(np.float32),
        "blp": rng.randint(1, 7, (W, S)).astype(np.int32),
        "is_gpu": np.tile([False] * CFG.n_cpu + [True], (W, 1)),
    }
    active = np.ones((W, S), bool)
    fam = FAMILY[:3]        # keep suite time down; digests cover all slices
    stk = sim.simulate_stacked(CFG, fam, pool, active,
                               n_cycles=600, warmup=100)
    for pol in fam:
        ref = sim.simulate(CFG, pol, pool, active, n_cycles=600, warmup=100)
        for k in ref:
            if k == "sim_steps":
                # driver property, not a policy metric: the stacked family
                # shares ONE loop, so its step count is the min over every
                # slice's witnesses — not any single policy's own count
                continue
            np.testing.assert_array_equal(
                ref[k], stk[pol][k], err_msg=f"{pol}:{k}")


# ---------------------------------------------------------------------------
# hot-loop invariant: one stacked step, sorts still only behind conds
# ---------------------------------------------------------------------------

def _stacked_step_jaxpr():
    pols, carry = sim._init_stacked(CFG, FAMILY)
    S = CFG.n_src
    pool = {k: jnp.zeros((S,), jnp.float32)
            for k in ("mpki", "inst_per_miss", "rbl")}
    pool.update(blp=jnp.ones((S,), jnp.int32),
                is_gpu=jnp.zeros((S,), bool))
    step = schedulers.make_stacked_step(CFG, pols,
                                        sim.prepare_pool(pool, (S,)),
                                        jnp.ones((S,), bool))
    return jax.make_jaxpr(step)(carry, jnp.int32(5))


def test_stacked_step_no_unconditional_sorts():
    """The whole family's cycle in ONE jaxpr, ranking still cond-gated."""
    jx = _stacked_step_jaxpr()
    prims = list(compat.walk_primitives(jx.jaxpr))
    uncond = [p for p, in_cond in prims if p in SORT_PRIMS and not in_cond]
    assert not uncond, (
        f"stacked step: {len(uncond)} unconditional sort op(s) — a policy's "
        f"ranking escaped its boundary cond on the stacked path")
    # non-vacuity: the ranked policies' boundary sorts are in there, gated
    gated = [p for p, in_cond in prims if p in SORT_PRIMS and in_cond]
    assert len(gated) >= 3, f"expected the family's ranking sorts: {gated}"


# ---------------------------------------------------------------------------
# schema + opt-in surface
# ---------------------------------------------------------------------------

def test_stackable_surface():
    assert set(FAMILY) == {"frfcfs", "atlas", "parbs", "tcm", "bliss",
                           "squash_prio"}
    assert not policy_api.is_stackable("sms", CFG)
    # sms_dash is a configured variant: configure() changes cfg, so it must
    # never slip into a stacked group even if marked stackable
    assert not policy_api.is_stackable("sms_dash", CFG)


def test_union_state_pads_and_rejects_collisions():
    pols = [policy_api.get(n) for n in FAMILY]
    padded = schedulers.stacked_union_state(CFG, pols)
    keys = set(padded[0])
    for p, s in zip(pols, padded):
        assert set(s) == keys, p.name
        for k, v in p.init_state(CFG).items():       # own state not padded
            assert s[k].shape == v.shape and s[k].dtype == v.dtype

    class Collider:
        name = "collider"

        def init_state(self, cfg):
            return {"pri_src": jnp.zeros((1,), jnp.float32)}   # wrong schema

    with pytest.raises(ValueError, match="collision"):
        schedulers.stacked_union_state(CFG, pols + [Collider()])
