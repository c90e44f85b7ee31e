"""sweep_host_s: host seconds of `run_sweep`'s own work per window sweep:
its `sweep.pools` and `sweep.rows` spans (`benchmarks.common.SPANS`),
summed per sweep and averaged over the window's sweeps. The harness's
first sweep of the cell is set-up's warm-up and is left out. None where
the program keeps no such spans."""
import sys
from collections import defaultdict

PARTS = ("sweep.pools", "sweep.rows")


def read(ctx):
    spans = getattr(sys.modules.get("benchmarks.common"), "SPANS", None)
    if spans is None:
        return None
    sweeps, host = [], defaultdict(float)
    for r in spans.records:
        if r["event"] == "sweep" and r.get("tag") == ctx["cell"].name:
            sweeps.append(r["id"])
        elif r["event"] in PARTS:
            host[r["sweep_id"]] += r.get("dur_s", 0.0)
    window = sweeps[1:]
    if not window:
        return None
    return sum(host[i] for i in window) / len(window)
