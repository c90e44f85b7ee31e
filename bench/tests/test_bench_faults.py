"""The comparison that decides `correct` against a broken timed path: a run
driven through the harness (the look for a chip skipped, at a tiny size on
the CPU) must read `correct: false` for each fault the cells can have, and
the reference's own control must fail the comparison too.

Faults planted under `run_sweep`, in the program's dispatch:
  * an answer altered where it is produced (one statistic of one row);
  * half of the batch left out, the mean taken over the rest (the first
    half simulated and repeated in place of the second);
  * a step that returns its state unchanged.
A run across chips has no exchange to leave out: both cells use one chip.
"""
import copy
import dataclasses
import io
import json
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import pytest

import cells
import reference
import run

SEED = 987654321987


def tiny_cell():
    cell = cells.load_cell("paper16.fig4")
    # two stackable policies (one stacked program) and sms (its own)
    traffic = dict(cell.traffic, population=dict(
        cell.traffic["population"], n_per_cat=1), n_cycles=40, warmup=10,
        policies=["frfcfs", "atlas", "sms"])
    return dataclasses.replace(cell, traffic=traffic)


def result_of(cell):
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.run(cell, SEED, 0.01, trace=False, platform="cpu") == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def altered(fn):
    def wrapped(*a, **k):
        out = dict(fn(*a, **k))
        v = out["completed"]                  # last row: a mix, not alone
        out["completed"] = v.at[(-1,) + (0,) * (v.ndim - 1)].add(1.0)
        return out
    return wrapped


def half_left_out(fn):
    def wrapped(cfg, pol, pool, active, *a, **k):
        w = active.shape[0]
        h = (w + 1) // 2
        out = fn(cfg, pol, {c: v[:h] for c, v in pool.items()}, active[:h],
                 *a, **k)
        return {c: jnp.concatenate([v, v[:w - h]]) for c, v in out.items()}
    return wrapped


@pytest.fixture
def fresh_jit():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_run_is_correct():
    assert result_of(tiny_cell())["correct"] is True


@pytest.mark.parametrize("fault", ["altered", "half_left_out"])
def test_broken_dispatch_is_caught(monkeypatch, fault):
    from repro.core import simulator as sim

    wrap = {"altered": altered, "half_left_out": half_left_out}[fault]
    for name in ("simulate_async", "simulate_stacked_async"):
        monkeypatch.setattr(sim, name, wrap(getattr(sim, name)))
    res = result_of(tiny_cell())
    assert res["correct"] is False
    c = res["checks"]
    assert c["value_mismatch"]["value"] > 0 or \
        c["measured_gap"]["value"] > c["measured_gap"]["limit"]


def test_state_left_unchanged_is_caught(monkeypatch, fresh_jit):
    from repro.core import policy, schedulers

    still = lambda *a, **k: (lambda carry, t: (carry, None))
    monkeypatch.setattr(policy, "make_step", still)
    monkeypatch.setattr(schedulers, "make_stacked_step", still)
    res = result_of(tiny_cell())
    assert res["correct"] is False
    assert res["checks"]["value_mismatch"]["value"] > 0


def test_bf16_control_fails_the_comparison():
    cell = tiny_cell()
    mixes = cells.population(cell, SEED, 1)
    raw = reference.raw_stats(cell.sim_fields, cell.policies, mixes,
                              cell.n_cycles, cell.warmup)
    ref = reference.assemble(cell.sim_fields, raw, mixes)
    c = reference.compare(reference.assemble(cell.sim_fields, raw, mixes,
                                             "bf16"), ref)
    assert c["values"] > 0 and c["mismatch"] > 0
    assert c["measured_gap"] > run.MEASURED_GAP_LIMIT
    assert reference.compare(ref, ref)["mismatch"] == 0


def _ref(**measured):
    return {"p": {"alone": {"a": 1.0}, "rows": [{"x": float("nan")}],
                  "agg": {"x": 2.0}, "by_category": {},
                  "measured": {"m": [1.0, 2.0], **measured},
                  "measured_bound": {"m": [0.5, 0.5],
                                     **{k: [1.0] * len(v)
                                        for k, v in measured.items()}}}}


def test_compare_rules():
    ref = _ref()
    prog = copy.deepcopy(ref)
    c = reference.compare(prog, ref)
    assert (c["values"], c["mismatch"], c["measured_gap"]) == (5, 0, 0.0)
    prog["p"]["measured"]["m"] = [1.25, 2.0]       # half its bound away
    assert reference.compare(prog, ref)["measured_gap"] == 0.5
    prog["p"]["measured"]["urgent_admits"] = [0.0, 0.0]   # union padding
    assert reference.compare(prog, ref)["measured_gap"] == 0.5
    prog["p"]["measured"]["urgent_admits"] = [0.0, 1.0]
    assert reference.compare(prog, ref)["by_part"] == {"p.measured": 2}
    prog = copy.deepcopy(ref)
    prog["p"]["agg"]["x"] = 2.0000001
    prog["p"]["rows"][0]["y"] = 1.0
    c = reference.compare(prog, ref)
    assert c["mismatch"] == 2 and c["by_part"] == {"p.agg": 1,
                                                   "p.extra": 1}
    for broken in ({"p": {"policy": "p", "error": "boom"}}, {}):
        c = reference.compare(broken, ref)
        assert c["mismatch"] == 5 and c["measured_gap"] == 0.0
    assert reference.same_tree({"a": [1.0, float("nan")]},
                               {"a": [1.0, float("nan")]})
    assert not reference.same_tree({"a": [1.0]}, {"a": [1.0, 2.0]})
