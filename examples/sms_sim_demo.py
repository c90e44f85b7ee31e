"""Inspect the SMS pipeline cycle-by-cycle on a tiny configuration.

  PYTHONPATH=src python examples/sms_sim_demo.py

Shows stage-1 batch formation (per-source FIFOs), stage-2 drains, and the
per-bank DCS occupancy over the first few hundred cycles.
"""
import sys

sys.path.insert(0, "src")

import numpy as np

from repro import compile_cache
from repro.core import simulator as sim
from repro.core.params import SimConfig


def main():
    compile_cache.enable()
    cfg = SimConfig(n_cpu=2, n_channels=1, buf_entries=28, fifo_size=6,
                    dcs_size=4)
    pool = {
        "mpki": np.asarray([30.0, 5.0, 1000.0], np.float32),
        "inst_per_miss": np.asarray([33.3, 200.0, 1.0], np.float32),
        "rbl": np.asarray([0.3, 0.8, 0.93], np.float32),
        "blp": np.asarray([4, 1, 4], np.int32),
        "is_gpu": np.asarray([False, False, True]),
    }
    active = np.ones(3, bool)
    st, sms, dram = sim.simulate_debug(cfg, "sms", pool, active,
                                       n_cycles=600)
    names = ["cpu.hi-blp", "cpu.hi-rbl", "gpu"]
    print("after 600 cycles:")
    print(f"{'source':11s} {'emitted':>8s} {'completed':>9s} "
          f"{'rowhits':>8s} {'issued':>7s} {'fifo_len':>8s}")
    for s, n in enumerate(names):
        print(f"{n:11s} {st['emitted'][s]:8d} {st['completed'][s]:9d} "
              f"{dram['hits'][s]:8d} {dram['issued'][s]:7d} "
              f"{sms['f_len'][0, s]:8d}")
    print(f"\nDCS per-bank queue lengths: {sms['d_len'][0].tolist()}")
    print(f"open rows per bank:        {dram['open_row'][0].tolist()}")
    gpu_rbl = dram['hits'][2] / max(dram['issued'][2], 1)
    print(f"\nGPU row-hit rate under SMS batching: {gpu_rbl:.2f} "
          f"(generator locality 0.93 — stage-1 batches preserve it)")


if __name__ == "__main__":
    main()
