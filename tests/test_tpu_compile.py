"""The headline sweep's programs compile for a TPU v5e chip.

Compiles `simulator._sim_batch` at the headline shape (parity config, 105
mixes plus the alone baselines, 16k+2k cycles), and the stacked family
program `simulator._sim_batch_stacked` and the two SMS programs at the
benchmark cell's shape (16 CPUs + 1 GPU on 4 channels, 134 entries, 128
rows; six policies stacked, `sms` and `sms_dash` one each), for one
chip of a described v5e:2x2 topology: nothing runs, but the TPU compiler
must accept each program and its memory must fit the chip. The topology is
described inside a module fixture (never at import, in a skipif or in
parametrize) and the persistent compilation cache is off around the
compiles.
"""
import os
import re

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


def _headline_args(sharding, cfg=None):
    """Shape structs of the headline batch (alone rows + workload rows),
    as `simulate_async` hands them to `_sim_batch`."""
    cfg = common.parity_config() if cfg is None else cfg
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
    _assert_fits(compiled)


def _assert_fits(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, mem


def test_stacked_family_compiles_for_v5e_without_select_gathers(one_chip):
    """The benchmark cell's stacked program: it fits the chip, and its
    optimized HLO keeps no gather under `select.eligibility` or
    `select.score` (their small-table lookups are one-hot selects)."""
    cfg = common.parity_config(n_cpu=16, n_channels=4)
    assert cfg.buf_entries == 134
    cfg, pool, active = _headline_args(one_chip, cfg)
    assert active.shape[0] == 128
    policies = sim.stackable_names(cfg)
    assert len(policies) == 6
    compiled = sim._sim_batch_stacked.lower(
        cfg, policies, 2_000, 500, sim.DEFAULT_UNROLL, False, pool,
        active).compile()
    _assert_fits(compiled)
    hlo = compiled.as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    assert any("/select.eligibility/" in o for o in op_names)
    gathers = [m.group(1) for m in re.finditer(
        r'= \S+ gather\(.*?op_name="([^"]*)"', hlo)]
    assert gathers                          # select.issue still gathers
    bad = [o for o in gathers
           if {"select.eligibility", "select.score"} & set(o.split("/"))]
    assert not bad, bad


@pytest.mark.parametrize("policy", ["sms", "sms_dash"])
def test_sms_program_compiles_for_v5e_without_stage_gathers(one_chip,
                                                            policy):
    """The benchmark cell's SMS programs: each fits the chip, and its
    optimized HLO keeps no gather and no reduce-window under
    `sms.stage1`-`sms.stage3` (their FIFO and DCS reads are one-hot
    selects, the front-run rescan a running AND)."""
    cfg = common.parity_config(n_cpu=16, n_channels=4)
    cfg, pool, active = _headline_args(one_chip, cfg)
    assert active.shape[0] == 128
    compiled = sim._sim_batch.lower(cfg, policy, 2_000, 500,
                                    sim.DEFAULT_UNROLL, False, pool,
                                    active).compile()
    _assert_fits(compiled)
    hlo = compiled.as_text()
    stages = {"sms.stage1", "sms.stage2", "sms.stage3"}
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    assert stages <= {c for o in op_names for c in o.split("/")}
    bad = [m.group(0)[:160] for m in re.finditer(
        r'= \S+ (?:gather|reduce-window)\(.*?op_name="([^"]*)"', hlo)
        if stages & set(m.group(1).split("/"))]
    assert not bad, bad
