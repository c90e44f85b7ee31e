"""Compile a cell's sweep programs for a described TPU v5e chip, with no chip.

    JAX_PLATFORMS=cpu python3 bench/compile_rehearsal.py --workload <cell>

Builds the cell's first window population and hands the shapes of its batch
(alone rows, then the mixes) to the program's jitted drivers as `run_sweep`
calls them: the stacked family program (`simulator._sim_batch_stacked`) for
the stackable policies and one `simulator._sim_batch` per other policy.
Each is lowered and compiled for one chip of a described v5e:2x2 topology;
prints the compile seconds on this host and the compiled program's memory.
Nothing runs. The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for p in (BENCH, BENCH.parent / "src", BENCH.parent):
    sys.path.insert(0, str(p))

import cells  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    a = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.core import params
    from repro.core import simulator as sim
    from repro.core import workloads as wl

    import run

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    cell = cells.load_cell(a.workload)
    f = dict(cell.sim_fields)
    cfg = params.SimConfig(timing=params.Timing(**f.pop("timing")), **f)
    mixes = run.program_mixes(cells.population(cell, 1, 1))
    pool, active = wl.pool_batch(cfg, mixes)
    apool, aactive, _ = wl.alone_batch(cfg)
    pool = {k: np.concatenate([apool[k], pool[k]]) for k in pool}
    active = np.concatenate([aactive, active])
    pool = sim.prepare_pool(pool, active.shape)
    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)
    pool, active = {k: spec(v) for k, v in pool.items()}, spec(active)
    stacked = sim.stackable_names(cfg, cell.policies)
    progs = [("stacked " + ",".join(stacked), sim._sim_batch_stacked,
              stacked)]
    progs += [(p, sim._sim_batch, p) for p in cell.policies
              if p not in stacked]
    for label, fn, pol in progs:
        t0 = time.perf_counter()
        compiled = fn.lower(cfg, pol, cell.n_cycles, cell.warmup,
                            sim.DEFAULT_UNROLL, False, pool,
                            active).compile()
        dt = time.perf_counter() - t0
        m = compiled.memory_analysis()
        print(f"{cell.name} {label}: compile {dt:.1f} s (this host, "
              f"described v5e); code {m.generated_code_size_in_bytes} B, "
              f"args {m.argument_size_in_bytes} B, out "
              f"{m.output_size_in_bytes} B, temp {m.temp_size_in_bytes} B",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
