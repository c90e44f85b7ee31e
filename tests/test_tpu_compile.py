"""The headline sweep's solo programs compile for a TPU v5e chip.

Compiles `simulator._sim_batch` at the headline shape (parity config, 105
mixes plus the alone baselines, 16k+2k cycles) for one chip of a described
v5e:2x2 topology: nothing runs, but the TPU compiler must accept each
program and its memory must fit the chip. The topology is described inside
a module fixture (never at import, in a skipif or in parametrize) and the
persistent compilation cache is off around the compiles.
"""
import os

import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from benchmarks import common
from repro.core import simulator as sim
from repro.core import workloads as wl

V5E_HBM_BYTES = 16 * 1024 ** 3      # Google Cloud "TPU v5e": 16 GB HBM/chip
N_PER_CAT, N_CYCLES, WARMUP = 15, 16_000, 2_000


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


def _headline_args(sharding):
    """Shape structs of the headline batch (alone rows + workload rows),
    as `simulate_async` hands them to `_sim_batch`."""
    cfg = common.parity_config()
    wls = wl.make_workloads(cfg.n_cpu, n_per_cat=N_PER_CAT)
    pool, active = wl.pool_batch(cfg, wls)
    apool, aactive, _ = wl.alone_batch(cfg)
    pool = {k: np.concatenate([apool[k], pool[k]]) for k in pool}
    active = np.concatenate([aactive, active])
    pool = sim.prepare_pool(pool, active.shape)
    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=sharding)
    return cfg, {k: spec(v) for k, v in pool.items()}, spec(active)


@pytest.mark.parametrize("policy,skip", [("sms", False), ("parbs", False),
                                         ("frfcfs", True)])
def test_headline_program_compiles_for_v5e(one_chip, policy, skip):
    cfg, pool, active = _headline_args(one_chip)
    assert active.shape[0] > 105            # alone rows ride the batch
    compiled = sim._sim_batch.lower(cfg, policy, N_CYCLES, WARMUP,
                                    sim.DEFAULT_UNROLL, skip, pool,
                                    active).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, mem
