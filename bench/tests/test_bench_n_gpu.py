"""Configurations with one GPU or none (`n_gpu` 1 or 0) in the benchmark's
frozen traffic and metric code (`simref`), the cell loader and the plain
reference.

With one GPU every bit stays as it was: the digests below were taken from
the code as it stood before a GPU-less configuration could be expressed,
of the fig4 populations at several (seed, sweep) pairs, of the sweep and
alone pools of `paper_16c4ch`, and of `reference.assemble` on a fixed
synthetic set of per-row statistics (keys and bits).

With none there is no GPU slot: HWA `j` sits at source `n_cpu + j`, there
are no GPU alone rows, and the GPU term leaves the metrics. A program that
builds a GPU such a cell does not have is caught, the fault planted here
over the program's own builders.
"""
import hashlib
import io
import json
import shutil
import struct
from contextlib import redirect_stdout
from pathlib import Path

import numpy as np
import pytest

import cells
import reference
import run
from simref import loop
from simref import metrics as rmet
from simref import workloads as rwl
from simref.params import CLS_CPU, CLS_GPU, CLS_HWA

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 11
FIG4 = "paper16.fig4"
PAIRS = ((SEED, 0), (SEED, 1), (SEED, 2), (3, 1), (987654321987, 5))
POPULATION_DIGESTS = {
    (SEED, 0): "8e2c8b1b0ee97947f24dc5f22bc893d873716ced",
    (SEED, 1): "76ac049f0dac4fd995f6810570e0f378b7823189",
    (SEED, 2): "57a4658ef44c59cf8bfd77488233807b0ae07954",
    (3, 1): "535f25fda0febdbadd290d0e4c5c52558132c188",
    (987654321987, 5): "f7fb003d2169bb57f6822740702360fa9e07eca5",
}
POOL_DIGEST = "c271361c02fb2249196c48b8773b05fe98e661b0"
ALONE_DIGEST = "15d884a110b398a4a3d2ad905938ef00a63af383"
ASSEMBLE_DIGEST = "033ec99f512dba9dd6bac31e10e6b8803a1410d2"
ASSEMBLE_POLICIES = ("frfcfs", "squash_prio")


# ---------------------------------------------------------------------------
# digests
# ---------------------------------------------------------------------------

def mixes_digest(mixes) -> str:
    rows = [[w.category, [int(i) for i in w.cpu_ids], int(w.gpu_id),
             [int(i) for i in w.hwa_ids]] for w in mixes]
    return hashlib.sha1(json.dumps(rows).encode()).hexdigest()


def arrays_digest(arrays) -> str:
    h = hashlib.sha1()
    for k in sorted(arrays):
        v = np.ascontiguousarray(arrays[k])
        h.update(f"{k}:{v.dtype.str}:{v.shape};".encode())
        h.update(v.tobytes())
    return h.hexdigest()


def tree_digest(tree) -> str:
    h = hashlib.sha1()
    for path, v in reference._leaves(tree, ""):
        h.update(path.encode())
        h.update(struct.pack("<d", float(v)))
    return h.hexdigest()


def synthetic_raw(cfg, n_rows: int, policies=ASSEMBLE_POLICIES):
    """Per-row statistics under `loop.simulate`'s keys and shapes, drawn
    from a fixed seed: what `reference.assemble` reduces."""
    rng = np.random.RandomState(12345)
    S, bins = cfg.n_src, cfg.lat_bins
    f32 = lambda lo, hi, *shape: rng.uniform(lo, hi, (n_rows,) + shape
                                             ).astype(np.float32)
    count = lambda hi, *shape: rng.randint(0, hi, (n_rows,) + shape
                                           ).astype(np.float32)
    out = {}
    for pol in policies:
        m = {"ipc": f32(0.01, 2.0, S), "bw": f32(0.001, 0.5, S),
             "mpkc": f32(0.0, 60.0, S), "rbl": f32(0.0, 1.0, S),
             "avg_lat": f32(11.0, 400.0, S), "completed": count(900, S),
             "emitted": count(900, S), "outstanding_end": count(9, S),
             "inflight_unserved": count(9, S), "dl_met": count(4, S),
             "dl_missed": count(4, S), "frames_released": count(5, S),
             "sim_steps": np.full(n_rows, 2000.0, np.float32),
             "lat_hist": count(50, S, bins),
             "energy_act": f32(0.0, 900.0, S), "energy_rw": f32(0.0, 900.0, S),
             "energy_bg": f32(0.0, 900.0), "energy_wake": f32(0.0, 90.0),
             "pd_cycles": count(2000)}
        if pol == "squash_prio":
            m["urgent_admits"] = count(30, S)
        out[pol] = m
    return out


def fig4_cell():
    return cells.load_cell(FIG4)


# ---------------------------------------------------------------------------
# one GPU: bit for bit as before
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,sweep", PAIRS)
def test_one_gpu_population_is_unchanged(seed, sweep):
    mixes = cells.population(fig4_cell(), seed, sweep)
    assert len(mixes) == 105 and all(0 <= w.gpu_id < 5 for w in mixes)
    assert mixes_digest(mixes) == POPULATION_DIGESTS[seed, sweep]


def test_one_gpu_pools_are_unchanged():
    cell = fig4_cell()
    cfg = reference.sim_config(cell.sim_fields)
    pool, active = rwl.pool_batch(cfg, cells.population(cell, SEED, 1))
    assert arrays_digest({**pool, "active": active}) == POOL_DIGEST
    apool, aactive, amap = rwl.alone_batch(cfg)
    assert arrays_digest({**apool, "active": aactive,
                          "map": np.asarray(json.dumps(amap))}) == \
        ALONE_DIGEST
    assert cells.n_alone_rows(cell) == len(amap) == 23
    assert cells.cycle_workloads(cell) == 2_560_000


def test_one_gpu_assemble_is_unchanged():
    cell = fig4_cell()
    cfg = reference.sim_config(cell.sim_fields)
    mixes = cells.population(cell, SEED, 1)
    raw = synthetic_raw(cfg, 23 + len(mixes))
    res = reference.assemble(cell.sim_fields, raw, mixes)
    assert "gpu_speedup" in res["frfcfs"]["rows"][0]
    assert tree_digest(res) == ASSEMBLE_DIGEST


# ---------------------------------------------------------------------------
# no GPU
# ---------------------------------------------------------------------------

def gpuless_fields(n_cpu=16, n_hwa=4, n_channels=4):
    fields = dict(fig4_cell().sim_fields)
    fields.update(n_cpu=n_cpu, n_gpu=0, n_hwa=n_hwa, n_channels=n_channels,
                  buf_entries=(n_cpu + n_hwa) * 6 + 8 * 4)
    return fields


def test_gpuless_population_keeps_the_other_draws():
    """No GPU draw: the CPU and HWA draws come in the order they would
    with one, less the GPU's."""
    got = rwl.make_workloads(4, n_per_cat=3, seed=77, n_hwa=2, n_gpu=0)
    rng = np.random.RandomState(77)
    by_group = {"l": [], "m": [], "h": []}
    for i, (name, *_) in enumerate(rwl.CPU_BENCH):
        by_group[name[0]].append(i)
    want = []
    for cat in rwl.CATEGORIES:
        pool = [i for g in rwl._CAT_GROUPS[cat] for i in by_group[g]]
        for _ in range(3):
            cpu = tuple(rng.choice(pool, size=4, replace=True))
            hwa = tuple(int(rng.randint(len(rwl.HWA_BENCH)))
                        for _ in range(2))
            want.append(rwl.Workload(cat, cpu, -1, hwa))
    assert got == want
    assert got != rwl.make_workloads(4, n_per_cat=3, seed=77, n_hwa=2)


def test_gpuless_pools_have_no_gpu_slot():
    cfg = reference.sim_config(gpuless_fields(n_hwa=2))
    mixes = rwl.make_workloads(16, n_per_cat=15, seed=5, n_hwa=2, n_gpu=0)
    pool, active = rwl.pool_batch(cfg, mixes)
    assert pool["mpki"].shape == (105, 18) and active.all()
    assert not pool["is_gpu"].any()
    assert not (pool["src_class"] == CLS_GPU).any()
    for w, wl in enumerate(mixes):
        assert wl.gpu_id == -1
        assert (pool["src_class"][w, :16] == CLS_CPU).all()
        for j, b in enumerate(wl.hwa_ids):
            _, period, reqs, rbl, blp, jit = rwl.HWA_BENCH[b]
            assert pool["src_class"][w, 16 + j] == CLS_HWA
            assert (pool["dl_period"][w, 16 + j], pool["dl_reqs"][w, 16 + j],
                    pool["dl_jitter"][w, 16 + j]) == (period, reqs, jit)
            assert pool["rbl"][w, 16 + j] == np.float32(rbl)


def test_gpuless_alone_rows_are_cpus_and_accelerators():
    cfg = reference.sim_config(gpuless_fields())
    apool, aactive, amap = rwl.alone_batch(cfg)
    assert list(amap) == [b[0] for b in rwl.CPU_BENCH] + \
        [h[0] for h in rwl.HWA_BENCH]
    assert len(amap) == 18 + 4 and aactive.sum() == 22
    assert not apool["is_gpu"].any()
    assert not (apool["src_class"] == CLS_GPU).any()
    hwa_rows = [amap[h[0]] for h in rwl.HWA_BENCH]
    assert (apool["src_class"][hwa_rows, 16] == CLS_HWA).all()
    assert aactive[hwa_rows, 16].all() and aactive[hwa_rows].sum() == 4
    # the alone lookup reads each accelerator's bandwidth at n_cpu
    bw = np.zeros((22, cfg.n_src), np.float32)
    bw[hwa_rows, 16] = [0.125, 0.25, 0.375, 0.5]
    ipc = np.zeros((22, cfg.n_src), np.float32)
    ipc[:18, 0] = np.arange(1, 19) / 16
    alone = rwl.alone_perf_lookup(cfg, {"bw": bw, "ipc": ipc}, amap)
    assert [alone[h[0]] for h in rwl.HWA_BENCH] == [0.125, 0.25, 0.375, 0.5]
    assert alone[rwl.CPU_BENCH[17][0]] == 18 / 16


def test_gpuless_workload_metrics():
    """Two CPUs and two accelerators, no GPU, on a hand-built vector."""
    cfg = reference.sim_config(gpuless_fields(n_cpu=2, n_hwa=2))
    wl = rwl.Workload("L", (0, 6), -1, (3, 1))
    alone = {rwl.CPU_BENCH[0][0]: 2.0, rwl.CPU_BENCH[6][0]: 1.0,
             rwl.HWA_BENCH[3][0]: 0.5, rwl.HWA_BENCH[1][0]: 0.25}
    shared = np.asarray([1.0, 0.25, 0.25, 0.25])
    got = rmet.workload_metrics(cfg, wl, shared, alone)
    # ratios: CPUs 0.5 and 0.25, accelerators 0.5 and 1.0
    assert got == {
        "weighted_speedup": 0.75, "cpu_weighted_speedup": 0.75,
        "max_slowdown": 4.0, "cpu_max_slowdown": 4.0,
        "harmonic_speedup": pytest.approx(4 / (2 + 4 + 2 + 1)),
        "hwa_speedup": 1.5, "hwa_max_slowdown": 2.0}
    assert list(rmet.per_source_alone(cfg, wl, alone)) == \
        [2.0, 1.0, 0.5, 0.25]


def test_gpuless_assemble_keeps_the_class_columns():
    """With no GPU a row has no `gpu_speedup`; the per-class QoS columns
    stay, the GPU's with no mass reading 0."""
    fields = gpuless_fields(n_hwa=2)
    cfg = reference.sim_config(fields)
    mixes = rwl.make_workloads(16, n_per_cat=1, seed=5, n_hwa=2, n_gpu=0)
    raw = synthetic_raw(cfg, 22 + len(mixes))
    row = reference.assemble(fields, raw, mixes)["frfcfs"]["rows"][0]
    one_gpu = reference.assemble(
        fig4_cell().sim_fields,
        synthetic_raw(reference.sim_config(fig4_cell().sim_fields), 30),
        cells.population(fig4_cell(), SEED, 1)[:7])["frfcfs"]["rows"][0]
    assert set(row) == (set(one_gpu) - {"gpu_speedup"}) | \
        {"hwa_speedup", "hwa_max_slowdown"}
    assert row["lat_p95_gpu"] == row["lat_p99_gpu"] == 0.0
    assert row["weighted_speedup"] == row["cpu_weighted_speedup"]


@pytest.mark.parametrize("policy", ["frfcfs", "sms", "squash_prio"])
def test_reference_runs_a_gpuless_soc(policy):
    """2 CPUs and 2 accelerators on one channel, 1,300 cycles from reset:
    every request emitted is completed or still in flight, and each
    accelerator counts one frame per period boundary passed."""
    fields = gpuless_fields(n_cpu=2, n_hwa=2, n_channels=1)
    cfg = reference.sim_config(fields)
    mixes = [rwl.Workload("H", (12, 17), -1, (3, 1))]   # ldpc 600, hog 800
    pool, active, amap = reference.batch(cfg, mixes)
    row = len(amap)
    n = 1300
    (r,) = loop.simulate_rows(fields, policy, pool, active, n, 0, [row])
    assert r["emitted"].sum() > 0
    assert np.array_equal(r["emitted"], r["completed"]
                          + r["inflight_unserved"])
    assert list(r["frames_released"]) == [0, 0, (n - 1) // 600,
                                          (n - 1) // 800]
    assert np.array_equal(r["dl_met"] + r["dl_missed"],
                          r["frames_released"])


def scratch_checkout(tmp_path, n_gpu=0):
    """A copy of the benchmark with a GPU-less SoC cell added as new files:
    4 CPUs and 2 accelerators per mix on 2 channels, 1 mix per category,
    40 + 10 cycles, two policies."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((root / "bench/configs/paper_16c4ch.json").read_text())
    conf["name"] = "soc_4c2x"
    conf["sim_config"].update(n_cpu=4, n_gpu=n_gpu, n_hwa=2, n_channels=2,
                              buf_entries=(4 + n_gpu + 2) * 6 + 8 * 4)
    (root / "bench/configs/soc_4c2x.json").write_text(json.dumps(conf))
    traffic = json.loads((root / "bench/traffic/fig4.json").read_text())
    traffic["population"].update(n_per_cat=1, hwa_per_mix=2)
    traffic.update(n_cycles=40, warmup=10, policies=["frfcfs", "sms"])
    (root / "bench/traffic/soc_tiny.json").write_text(json.dumps(traffic))
    bench["configs"].append({
        "name": "soc_4c2x", "source": "https://arxiv.org/abs/1505.07502",
        "file": "bench/configs/soc_4c2x.json", "reduced": [],
        "why": "4 CPUs and 2 frame-deadline accelerators, no GPU"})
    bench["workloads"].append({
        "name": "soc.tiny", "config": "soc_4c2x", "traffic": "soc_tiny",
        "chips": 1, "why": "CPUs and accelerators"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("n_gpu", [2, -1])
def test_load_cell_refuses_gpu_counts_it_cannot_model(tmp_path, n_gpu):
    root = scratch_checkout(tmp_path, n_gpu=n_gpu)
    with pytest.raises(ValueError, match="n_gpu"):
        cells.load_cell("soc.tiny", root=root)
    assert cells.load_cell(FIG4, root=root).sim_fields["n_gpu"] == 1


def test_gpuless_cell_is_found_by_name(tmp_path):
    root = scratch_checkout(tmp_path)
    cell = cells.load_cell("soc.tiny", root=root)
    mixes = cells.population(cell, SEED, 1)
    assert [w.category for w in mixes] == list(rwl.CATEGORIES)
    assert all(w.gpu_id == -1 and len(w.hwa_ids) == 2 and
               len(w.cpu_ids) == 4 for w in mixes)
    assert mixes == rwl.make_workloads(
        4, n_per_cat=1, seed=cells.population_seed(SEED, 1), n_hwa=2,
        n_gpu=0)
    assert cells.n_alone_rows(cell) == 18 + 4
    assert cells.cycle_workloads(cell) == 50 * (7 + 22) * 2


def test_per_layer_metrics_apply_to_every_cell(tmp_path):
    """No per-layer metric is tied to a cell: a GPU-less cell added as new
    files gets all nine, and fig4 the nine it had."""
    nine = {"device_idle_share": "fraction", "trace_lower_s": "s",
            "compile_load_s": "s", "stacked_us_per_cycle": "us",
            "solo_us_per_cycle": "us", "stacked_admit_us_per_cycle": "us",
            "stacked_select_us_per_cycle": "us",
            "stacked_engine_us_per_cycle": "us", "sweep_host_s": "s"}
    root = scratch_checkout(tmp_path)
    assert cells.load_cell("soc.tiny", root=root).per_layer == nine
    assert cells.load_cell(FIG4).per_layer == nine


def plant_gpu(pool, rows, slot, bench):
    """Writes GPU `bench` of the program's table at source `slot` of
    `rows`."""
    from repro.core import workloads as wl

    _, rbl, blp = wl.GPU_BENCH[bench]
    pool["mpki"][rows, slot], pool["rbl"][rows, slot] = 1000.0, rbl
    pool["blp"][rows, slot], pool["inst_per_miss"][rows, slot] = blp, 1.0
    pool["is_gpu"][rows, slot], pool["src_class"][rows, slot] = True, CLS_GPU
    for k in ("dl_period", "dl_reqs", "dl_jitter"):
        pool[k][rows, slot] = 0


def with_gpu_slot(pool_batch):
    """The program's `pool_batch`, then a GPU at slot `n_cpu` of every
    mix."""
    def wrapped(cfg, mixes):
        pool, active = pool_batch(cfg, mixes)
        pool = {k: np.array(v) for k, v in pool.items()}
        plant_gpu(pool, slice(None), cfg.n_cpu, 0)
        return pool, active
    return wrapped


def with_gpu_alone_rows(alone_batch):
    """The program's `alone_batch`, then an alone row for each GPU of the
    program's table that it lacks, the GPU at slot `n_cpu`."""
    from repro.core import workloads as wl

    def wrapped(cfg):
        pool, active, amap = alone_batch(cfg)
        gpus = [i for i, g in enumerate(wl.GPU_BENCH) if g[0] not in amap]
        extra = {k: np.repeat(v[:1], len(gpus), axis=0)
                 for k, v in pool.items()}
        extra_active = np.zeros((len(gpus),) + active.shape[1:], bool)
        amap = dict(amap)
        for r, i in enumerate(gpus):
            plant_gpu(extra, r, cfg.n_cpu, i)
            extra_active[r, cfg.n_cpu] = True
            amap[wl.GPU_BENCH[i][0]] = len(amap)
        pool = {k: np.concatenate([pool[k], extra[k]]) for k in pool}
        return pool, np.concatenate([active, extra_active]), amap
    return wrapped


def test_program_building_a_gpu_the_cell_lacks_is_caught(tmp_path,
                                                         monkeypatch):
    """A program that builds a GPU the cell does not have, planted over
    the program's own builders (a GPU at slot `n_cpu` of every mix, GPU
    alone rows), reads `correct: false` through `traffic_mismatch`, in the
    check and in a whole run, whatever the program does with no GPU."""
    from repro.core import params
    from repro.core import workloads as wl

    monkeypatch.setattr(wl, "pool_batch", with_gpu_slot(wl.pool_batch))
    monkeypatch.setattr(wl, "alone_batch",
                        with_gpu_alone_rows(wl.alone_batch))
    cell = cells.load_cell("soc.tiny", root=scratch_checkout(tmp_path))
    f = dict(cell.sim_fields)
    cfg = params.SimConfig(timing=params.Timing(**f.pop("timing")), **f)
    mixes = cells.population(cell, SEED, 1)
    assert run.traffic_mismatch(cfg, reference.sim_config(cell.sim_fields),
                                mixes) > 0
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.run(cell, SEED, 0.01, trace=False, platform="cpu") == 0
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is False
    assert res["checks"]["traffic_mismatch"]["value"] > 0
