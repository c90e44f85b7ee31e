"""The cycle step's named scopes (`policy.STEP_SCOPES`) are trace-time
metadata only.

  * For every policy, under both drivers (ticked, skipping) and on both
    paths (per-policy, and stacked where the policy is stackable), the
    per-cycle jaxpr runs the same primitives, in the same order, with the
    scopes as with every `jax.named_scope` turned into a no-op; the scopes
    are there (the check is not vacuous); and the final state still matches
    the golden digests.
  * Compiled at a tiny shape on XLA:CPU, the stacked family program and the
    `sms` program carry every scope of the vocabulary in some instruction's
    `op_name`, and no scope outside it: the device trace's reduction
    (`bench/stage_trace.py`) reads the stages from there.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import golden
from repro.core import policy as policy_api
from repro.core import schedulers
from repro.core import simulator as sim
from repro.core import workloads as wl

CFG = golden.CFG
GOLDEN = golden.load()
STACKABLE = sim.stackable_names(CFG)
CASES = [(p, skip, "solo") for p in policy_api.names()
         for skip in (False, True)] + \
        [(p, skip, "stacked") for p in STACKABLE for skip in (False, True)]
SCOPE_RE = re.compile(r"^(step|pol|select|sms)\.")


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in compat.sub_jaxprs(v):
                yield from _eqns(sub)


def _cycle_jaxpr(name, skip, path):
    """The per-cycle body of one policy, as the simulator builds it."""
    pool = sim.prepare_pool(golden.pool(CFG), (CFG.n_src,))
    active = jnp.ones((CFG.n_src,), bool)
    if path == "solo":
        cfg, pol, carry = sim._init(CFG, name)
        make = policy_api.make_skip_step if skip else policy_api.make_step
        body = make(cfg, pol, pool, active)
    else:
        pols, carry = sim._init_stacked(CFG, (name,))
        make = schedulers.make_stacked_skip_step if skip \
            else schedulers.make_stacked_step
        body = make(CFG, pols, pool, active)
    args = (carry, jnp.int32(5)) + ((jnp.int32(100),) if skip else ())
    return jax.make_jaxpr(body)(*args).jaxpr


@pytest.mark.parametrize("name,skip,path", CASES)
def test_scopes_add_no_primitive(name, skip, path, monkeypatch):
    scoped = list(_eqns(_cycle_jaxpr(name, skip, path)))
    stacks = {c for e in scoped
              for c in str(e.source_info.name_stack).split("/")}
    want = {"step.engine", "step.admit", "step.select"} | \
        ({"step.skip"} if skip else set()) | \
        ({"select.score"} if path == "stacked" else {"pol.select"}) | \
        ({"sms.stage1", "sms.stage2", "sms.stage3"}
         if name.startswith("sms") else set())
    assert want <= stacks, f"scopes missing: {sorted(want - stacks)}"

    monkeypatch.setattr(jax, "named_scope",
                        lambda _: contextlib.nullcontext())
    plain = list(_eqns(_cycle_jaxpr(name, skip, path)))
    assert not {c for e in plain
                for c in str(e.source_info.name_stack).split("/")
                if SCOPE_RE.match(c)}
    assert len(scoped) == len(plain)
    assert [e.primitive.name for e in scoped] == \
        [e.primitive.name for e in plain]

    if name in GOLDEN:
        active = np.ones(CFG.n_src, bool)
        if path == "solo":
            state = sim.simulate_debug(CFG, name, golden.pool(CFG), active,
                                       n_cycles=golden.N_CYCLES, skip=skip)
        else:
            state = sim.simulate_debug_stacked(
                CFG, (name,), golden.pool(CFG), active,
                n_cycles=golden.N_CYCLES, skip=skip)[name]
        bad = golden.compare(name, state, GOLDEN[name])
        assert not bad, bad


def _op_scopes(hlo: str):
    return {c for op in re.findall(r'op_name="([^"]*)"', hlo)
            for c in op.split("/") if SCOPE_RE.match(c)}


def test_every_scope_reaches_compiled_op_names():
    """Telemetry and the sanitizer on and the skipping driver, so that
    every scope is traced."""
    cfg = CFG.replace(telemetry_enabled=True, validate_enabled=True)
    pool, active = wl.pool_batch(cfg, wl.make_workloads(cfg.n_cpu,
                                                        n_per_cat=1)[:2])
    pool = sim.prepare_pool(pool, active.shape)
    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    pool = {k: spec(v) for k, v in pool.items()}
    active = spec(active)
    stacked = _op_scopes(sim._sim_batch_stacked.lower(
        cfg, STACKABLE, 20, 5, 1, True, pool, active).compile().as_text())
    solo = _op_scopes(sim._sim_batch.lower(
        cfg, "sms", 20, 5, 1, True, pool, active).compile().as_text())
    assert stacked | solo == set(policy_api.STEP_SCOPES)
    assert {"step.engine", "step.admit", "step.select", "step.skip",
            "step.telemetry", "step.validate"} <= stacked & solo
    assert {s for s in stacked if s.startswith("select.")} == \
        {"select.eligibility", "select.score", "select.issue",
         "select.clear"}
    assert {"pol.tick", "pol.select", "sms.stage1", "sms.stage2",
            "sms.stage3"} <= solo
