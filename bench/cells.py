"""The benchmark's data, found by name: cells, configurations, traffic mixes.

`BENCHMARK.json` names each cell's configuration and traffic mix; this
module reads

    bench/configs/<config>.json    the deployment: `SimConfig` fields, source,
                                   what was cut and what was assumed
    bench/traffic/<traffic>.json   the mix: population rule, policies, cycles,
                                   warm-up

and draws each sweep's population from `(seed, sweep index)` with the
benchmark's own frozen sampler (`simref.workloads`), so a later change to the
program's traffic tables cannot move the yardstick. Nothing here imports the
program.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Tuple

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # bench/configs/<name>.json, parsed
    traffic: Dict[str, Any]         # bench/traffic/<name>.json, parsed
    end_to_end: Dict[str, str]      # metric name -> unit, this cell's
    per_layer: Dict[str, str]
    root: Path = ROOT               # checkout holding BENCHMARK.json

    @property
    def sim_fields(self) -> Dict[str, Any]:
        return self.config["sim_config"]

    @property
    def policies(self) -> Tuple[str, ...]:
        return tuple(self.traffic["policies"])

    @property
    def n_cycles(self) -> int:
        return int(self.traffic["n_cycles"])

    @property
    def warmup(self) -> int:
        return int(self.traffic["warmup"])


def _load(path: Path) -> Dict[str, Any]:
    with path.open() as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return _load(root / "BENCHMARK.json")


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell `name` of `BENCHMARK.json`, with its files read by name.
    A configuration has one GPU or none: the reference models no more."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _load(root / conf["file"])
    n_gpu = config["sim_config"]["n_gpu"]
    if n_gpu not in (0, 1):
        raise ValueError(
            f"configuration {conf['name']!r} ({conf['file']}) has n_gpu "
            f"{n_gpu!r}; the benchmark models one GPU (1) or none (0)")
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=_load(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        end_to_end={m["name"]: m["unit"] for m in bench["end_to_end"]
                    if _applies(m, name)},
        per_layer={m["name"]: m["unit"] for m in bench["per_layer"]
                   if _applies(m, name)},
        root=root)


def population_seed(seed: int, sweep: int) -> int:
    """A 32-bit sampler seed from the run's seed (any size, any sign) and
    the sweep index, so no two sweeps of a run share their mixes."""
    ss = np.random.SeedSequence([seed % (1 << 64), sweep])
    return int(ss.generate_state(1)[0])


def population(cell: Cell, seed: int, sweep: int) -> List[Any]:
    """The mixes of sweep `sweep` (`simref.workloads.Workload`s): every
    seed gives the same number of mixes per category, with other draws."""
    from simref import workloads as rwl

    pop = cell.traffic["population"]
    return rwl.make_workloads(int(cell.sim_fields["n_cpu"]),
                              n_per_cat=int(pop["n_per_cat"]),
                              seed=population_seed(seed, sweep),
                              n_hwa=int(pop["hwa_per_mix"]),
                              n_gpu=int(cell.sim_fields["n_gpu"]))


def n_alone_rows(cell: Cell) -> int:
    from simref import workloads as rwl

    f = cell.sim_fields
    return len(rwl.CPU_BENCH) + \
        (len(rwl.GPU_BENCH) if int(f["n_gpu"]) > 0 else 0) + \
        (len(rwl.HWA_BENCH) if int(f["n_hwa"]) > 0 else 0)


def cycle_workloads(cell: Cell) -> int:
    """Simulated cycle-workloads of one sweep: (n_cycles + warmup) x
    (mix rows + alone rows) x policies."""
    n_mix = 7 * int(cell.traffic["population"]["n_per_cat"])
    return (cell.n_cycles + cell.warmup) * (n_mix + n_alone_rows(cell)) \
        * len(cell.policies)
