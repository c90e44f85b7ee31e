"""The control of a cell's `correct`: the reference, put in the program's
place and computed with its statistics in bfloat16, the precision below the
f32 the configuration states, must fail the comparison.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

For each seed, every row of window sweep 1's population is simulated once by
the plain reference (`reference.raw_stats`); the sweep result is rebuilt from
those statistics as they are and rounded to bfloat16, and the second is
compared with the first exactly as a run compares the program's
(`reference.compare`). Prints one line per seed with the numbers a run
compares (`value_mismatch`, `measured_gap`); a control reading no more than
a sound run would mean the comparison cannot see the lower precision. The
benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import cells  # noqa: E402
import reference  # noqa: E402


def control_reading(cell: cells.Cell, seed: int):
    """`reference.compare` of the bf16 control against the reference."""
    mixes = cells.population(cell, seed, 1)
    raw = reference.raw_stats(cell.sim_fields, cell.policies, mixes,
                              cell.n_cycles, cell.warmup)
    ref = reference.assemble(cell.sim_fields, raw, mixes)
    bf16 = reference.assemble(cell.sim_fields, raw, mixes, "bf16")
    return reference.compare(bf16, ref)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args()
    cell = cells.load_cell(a.workload)
    for seed in a.seeds:
        t0 = time.perf_counter()
        c = control_reading(cell, seed)
        print(f"control bf16 {cell.name} seed {seed}: value_mismatch="
              f"{c['mismatch']} measured_gap={c['measured_gap']!r} of "
              f"{c['values']} values in {time.perf_counter() - t0:.1f} s; "
              f"by part {c['by_part']}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
