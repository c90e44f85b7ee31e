"""Quickstart: the paper's SMS vs the baselines, in ~50 lines.

  PYTHONPATH=src python examples/quickstart.py

Every scheduler is a `MemoryPolicy` object in a registry
(`repro.core.policy`); `simulator.POLICIES` is just the registry's
enumeration. Writing a new policy is: subclass `CentralizedPolicy`, override
`score` (and optionally `extra_state` / `policy_tick` / `on_issue`),
decorate with `@policy.register` — the simulator, every benchmark sweep, and
the invariant tests pick it up by name with no other changes. `Oldest`
below is a complete example.
"""
import sys

sys.path.insert(0, "src")

import numpy as np

from repro import compile_cache
from repro.core import metrics as met
from repro.core import policy
from repro.core import simulator as sim
from repro.core import workloads as wl
from repro.core.params import SimConfig
from repro.core.schedulers import CentralizedPolicy, base_score


@policy.register
class Oldest(CentralizedPolicy):
    """Pure FCFS: age only, ignoring row hits — a 5-line custom policy."""

    name = "oldest"

    def score(self, cfg, pool, buf, is_hit, t):
        return base_score(cfg, buf, 0 * is_hit, t)


def main():
    compile_cache.enable()
    # 4 CPU cores + 1 GPU sharing 2 memory channels, high-intensity mix
    cfg = SimConfig(n_cpu=4, n_channels=2, buf_entries=72, fifo_size=8,
                    dcs_size=4)
    wls = [w for w in wl.make_workloads(cfg.n_cpu, n_per_cat=3, seed=0)
           if w.category in ("H", "HM")]
    pool, active = wl.pool_batch(cfg, wls)
    apool, aactive, amap = wl.alone_batch(cfg)

    print(f"{len(wls)} workloads x {cfg.n_src} sources, "
          f"{cfg.n_channels} channels\n")
    print(f"{'policy':12s} {'WS':>6s} {'cpuWS':>6s} {'gpuSU':>6s} {'maxSD':>6s}")
    # registry enumeration: the built-ins + the Oldest policy defined above
    for pol in policy.names():
        am = sim.simulate(cfg, pol, apool, aactive, 8_000, 1_000)
        alone = wl.alone_perf_lookup(cfg, am, amap)
        m = sim.simulate(cfg, pol, pool, active, 8_000, 1_000)
        perf = sim.perf_vector(cfg, m, pool)
        rows = [met.workload_metrics(cfg, w, perf[i], alone)
                for i, w in enumerate(wls)]
        a = met.aggregate(rows)
        print(f"{pol:12s} {a['weighted_speedup']:6.3f} "
              f"{a['cpu_weighted_speedup']:6.3f} {a['gpu_speedup']:6.3f} "
              f"{a['max_slowdown']:6.2f}")
    print("\nExpected: SMS best WS and (much) best max-slowdown — the "
          "paper's Fig 4 in miniature.")


if __name__ == "__main__":
    main()
