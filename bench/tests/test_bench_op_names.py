"""The stacked program's instruction op_names (`stage_trace.stacked_op_names`)
are built from the benchmark's own mixes, as the window hands them to the
program: the same map as the program's own draws give for a cell with a
GPU, and a map for a cell with none without any GPU draw, at tiny sizes on
the CPU."""
import dataclasses

import numpy as np

import cells
import stage_trace as stt
from test_bench_n_gpu import scratch_checkout


def stacked_hlo_from_program_draws(cell):
    """The optimized HLO the map was built from when it took the program's
    own `make_workloads` draws."""
    from repro.core import params
    from repro.core import simulator as sim
    from repro.core import workloads as wl

    f = dict(cell.sim_fields)
    cfg = params.SimConfig(timing=params.Timing(**f.pop("timing")), **f)
    pop = cell.traffic["population"]
    mixes = wl.make_workloads(cfg.n_cpu, int(pop["n_per_cat"]),
                              n_hwa=int(pop["hwa_per_mix"]))
    pool, active = wl.pool_batch(cfg, mixes)
    apool, aactive, _ = wl.alone_batch(cfg)
    pool = {k: np.concatenate([apool[k], pool[k]]) for k in pool}
    active = np.concatenate([aactive, active])
    lowered = sim._sim_batch_stacked.lower(
        cfg, sim.stackable_names(cfg, cell.policies), cell.n_cycles,
        cell.warmup, sim.DEFAULT_UNROLL, sim.DEFAULT_SKIP,
        sim.prepare_pool(pool, active.shape), active)
    return lowered.compile().as_text()


def test_fig4_map_is_the_program_draws_map(monkeypatch):
    """The same program, instruction for instruction, so the same map."""
    cell = cells.load_cell("paper16.fig4")
    cell = dataclasses.replace(cell, traffic=dict(
        cell.traffic, population=dict(cell.traffic["population"],
                                      n_per_cat=1),
        n_cycles=40, warmup=10, policies=["frfcfs", "atlas"]))
    texts = []
    hlo_op_names = stt.hlo_op_names
    monkeypatch.setattr(stt, "hlo_op_names",
                        lambda t: texts.append(t) or hlo_op_names(t))
    names = stt.stacked_op_names(cell)
    assert {stt.step_scope(v) for v in names.values()} >= \
        {"step.engine", "step.admit", "step.select"}
    before = stacked_hlo_from_program_draws(cell)
    assert texts == [before]
    assert names == hlo_op_names(before)


def test_gpuless_cell_is_lowered_with_no_gpu_draw(tmp_path, monkeypatch):
    """The GPU-less scratch cell's mixes reach the program with `gpu_id`
    -1, so a program that refuses a GPU draw where it has no GPU lowers
    it too."""
    from repro.core import workloads as wl

    seen = []
    pool_batch = wl.pool_batch

    def no_gpu_draw(cfg, mixes):
        if cfg.n_gpu == 0 and any(m.gpu_id >= 0 for m in mixes):
            raise ValueError("a GPU draw in a configuration with no GPU")
        seen.extend(mixes)
        return pool_batch(cfg, mixes)

    monkeypatch.setattr(wl, "pool_batch", no_gpu_draw)
    cell = cells.load_cell("soc.tiny", root=scratch_checkout(tmp_path))
    names = stt.stacked_op_names(cell)
    assert len(seen) == 7 and {m.gpu_id for m in seen} == {-1}
    assert [len(m.hwa_ids) for m in seen] == [2] * 7
    assert {stt.step_scope(v) for v in names.values()} >= \
        {"step.engine", "step.admit", "step.select"}
