"""The plain reference (`simref.loop`) against the program on paths the
benchmark's cell does not drive: three requester classes, with the frame
deadlines, SQUASH's urgent tier and SMS-DASH's preemption live. Every
statistic of every row must equal the program's bit for bit, but for the
two f32 sums whose rounding follows XLA's fusion (`energy_bg`,
`energy_wake`), which `reference.compare` holds to a rounding bound."""
import numpy as np
import pytest

import cells
import reference
from simref import loop
from simref import workloads as rwl

FUSED = ("energy_bg", "energy_wake")


def three_class_fields():
    fields = dict(cells.load_cell("paper16.fig4").sim_fields)
    fields.update(n_cpu=2, n_hwa=2, n_channels=1, buf_entries=5 * 6 + 8 * 4)
    return fields


@pytest.mark.parametrize("policy", ["squash_prio", "sms_dash"])
def test_reference_equals_program_with_accelerators(policy):
    from repro.core import params
    from repro.core import simulator as sim

    fields = three_class_fields()
    f = dict(fields)
    cfg = params.SimConfig(timing=params.Timing(**f.pop("timing")), **f)
    mixes = rwl.make_workloads(2, n_per_cat=1, seed=11, n_hwa=2)[:3]
    pool, active, _ = reference.batch(reference.sim_config(fields), mixes)
    rows = [0, 20, 23, 24, 25]            # CPU, GPU, HWA alone; two mixes
    pool = {k: v[rows] for k, v in pool.items()}
    active = active[rows]
    n_cycles, warmup = 1200, 300
    prog = sim.simulate(cfg, policy, pool, active, n_cycles, warmup)
    ref = loop.simulate_rows(fields, policy, pool, active, n_cycles, warmup,
                             range(len(rows)))
    assert sum(r["dl_met"].sum() + r["dl_missed"].sum() for r in ref) > 0
    for i, r in enumerate(ref):
        for k, v in r.items():
            p = np.asarray(prog[k])[i]
            if k in FUSED:
                assert abs(float(p) - float(v)) <= 4 * 2**-24 * abs(float(v))
            else:
                assert np.array_equal(p, v), (policy, i, k)
