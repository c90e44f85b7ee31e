"""CPU rehearsal of the benchmark: each cell's set-up, window, check and last
line at a tiny size, the refusal without a TPU, the traffic guard, and the
harness finding a new configuration, traffic mix and metric by name.

The tiny size is set here, on the cell record, never through an option of
`bench/run.py`: 1 mix per category, 40 + 10 cycles.
"""
import dataclasses
import io
import json
import shutil
import subprocess
import sys
from contextlib import redirect_stdout
from pathlib import Path

import pytest

import cells
import run

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 11          # larger than 32 signed bits hold
CELLS = ("paper16.fig4",)


def tiny(cell: cells.Cell) -> cells.Cell:
    pop = dict(cell.traffic["population"], n_per_cat=1)
    return dataclasses.replace(cell, traffic=dict(
        cell.traffic, population=pop, n_cycles=40, warmup=10))


def run_capture(cell, seed=SEED, trace=False, platform="cpu"):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.run(cell, seed, 0.01, trace=trace, platform=platform)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal(name):
    cell = tiny(cells.load_cell(name))
    rc, res = run_capture(cell)
    assert rc == 0
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] == len(cell.policies) and res["failed"] == 0
    assert set(res["metrics"]) == {"cycle_wl_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["metrics"]["cycle_wl_per_s"]["unit"] == "cycle-wl/s"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(res["device"])
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert {k: c["limit"] for k, c in res["checks"].items()} == {
        "traffic_mismatch": 0, "value_mismatch": 0,
        "measured_gap": run.MEASURED_GAP_LIMIT, "error_slices": 0}


def test_refuses_without_tpu(capsys):
    cell = tiny(cells.load_cell(CELLS[0]))
    rc, res = run_capture(cell, platform="tpu")
    assert rc == run.EXIT_NO_CHIP and res is None
    assert "no fallback" in capsys.readouterr().err


def test_command_refuses_on_cpu(tmp_path):
    """The command itself, as `BENCHMARK.json` gives it, in a directory
    holding only BENCHMARK.json and bench/: non-zero exit, no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_populations_differ_by_sweep_and_repeat_by_seed():
    cell = cells.load_cell(CELLS[0])
    a = cells.population(cell, SEED, 1)
    assert a == cells.population(cell, SEED, 1)
    assert a != cells.population(cell, SEED, 2)
    assert a != cells.population(cell, SEED + 1, 1)
    assert [w.category for w in a] == \
        [w.category for w in cells.population(cell, 3, 1)]
    assert cells.cycle_workloads(cell) == 2_560_000


@pytest.mark.parametrize("name", CELLS)
def test_frozen_traffic_reproduces_program_pools(name):
    """The benchmark's own tables and sampler give the program's mixes and
    pools at seed 7."""
    from repro.core import workloads as wl
    from simref import workloads as rwl

    import reference
    from repro.core import params

    cell = cells.load_cell(name)
    f = dict(cell.sim_fields)
    cfg = params.SimConfig(timing=params.Timing(**f.pop("timing")), **f)
    pop = cell.traffic["population"]
    mine = rwl.make_workloads(cfg.n_cpu, pop["n_per_cat"], seed=7,
                              n_hwa=pop["hwa_per_mix"])
    theirs = wl.make_workloads(cfg.n_cpu, pop["n_per_cat"], seed=7,
                               n_hwa=pop["hwa_per_mix"])
    assert [dataclasses.astuple(w) for w in mine] == \
        [dataclasses.astuple(w) for w in theirs]
    assert run.traffic_mismatch(cfg, reference.sim_config(cell.sim_fields),
                                mine) == 0


def test_perturbed_table_makes_run_incorrect(monkeypatch):
    from simref import workloads as rwl

    cell = tiny(cells.load_cell(CELLS[0]))
    table = list(rwl.CPU_BENCH)
    name, mpki, rbl, blp = table[0]
    table[0] = (name, mpki + 1.0, rbl, blp)
    monkeypatch.setattr(rwl, "CPU_BENCH", table)
    rc, res = run_capture(cell)
    assert rc == 0
    assert res["checks"]["traffic_mismatch"]["value"] > 0
    assert res["correct"] is False


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files, with entries in BENCHMARK.json, are found with no edit to any
    existing file of the benchmark."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}

    conf = json.loads((root / "bench/configs/paper_16c4ch.json").read_text())
    conf["name"] = "soc_16c1g2x"
    conf["sim_config"].update(n_hwa=2, buf_entries=19 * 6 + 8 * 4)
    (root / "bench/configs/soc_16c1g2x.json").write_text(json.dumps(conf))
    traffic = json.loads((root / "bench/traffic/fig4.json").read_text())
    traffic["population"].update(n_per_cat=2, hwa_per_mix=2)
    (root / "bench/traffic/qos_small.json").write_text(json.dumps(traffic))
    (root / "bench/metrics/sweeps_in_window.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["configs"].append({
        "name": "soc_16c1g2x", "source": "https://arxiv.org/abs/1505.07502",
        "file": "bench/configs/soc_16c1g2x.json", "reduced": [],
        "why": "16 CPUs, a GPU and 2 frame-deadline accelerators"})
    bench["workloads"].append({
        "name": "soc16.qos", "config": "soc_16c1g2x",
        "traffic": "qos_small", "chips": 1, "why": "three classes"})
    bench["per_layer"].append({
        "name": "sweeps_in_window", "unit": "sweeps", "better": "higher",
        "source": "program_counter", "layer": "sweep harness",
        "moves": "cycle_wl_per_s", "workloads": ["soc16.qos"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load_cell("soc16.qos", root=root)
    fig4 = cells.load_cell(CELLS[0], root)
    assert cell.sim_fields["n_hwa"] == 2
    assert cell.traffic["population"]["n_per_cat"] == 2
    assert cell.per_layer == {**fig4.per_layer, "sweeps_in_window": "sweeps"}
    assert set(cell.end_to_end) == {"cycle_wl_per_s", "setup_s"}
    assert run.load_metric_reader("sweeps_in_window", root)({}) == 42.0
    assert cells.cycle_workloads(cell) == 2500 * (14 + 27) * 8
    assert "sweeps_in_window" not in fig4.per_layer
    after = {p: p.read_bytes() for p in before}
    assert after == before
    mix = cells.population(cell, 5, 1)[0]
    assert mix.category == "L" and len(mix.hwa_ids) == 2
