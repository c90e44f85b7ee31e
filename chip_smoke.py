"""Chip smoke run: the headline SMS design sweep on one TPU chip.

    python chip_smoke.py

Runs in one process and starts no other. Phases, in order; the first that
fails ends the run with a non-zero exit code:

1. device    — platform, kind and count from `jax.devices()`. A platform
               other than tpu exits with code 2: there is no CPU fallback.
2. golden    — `simulate_debug` final state of every policy in
               tests/golden_policy_states.json against its digests, then
               the stacked family's slices: golden policies against the
               digests, the others against their solo chip run and a CPU
               run of the same program. On a mismatch the first diverging
               cycle is located by bisection against the CPU.
3. reference — a reduced sweep (every registry policy, parity config, one
               mix per category) through `common.run_sweep` on the chip
               and on the host CPU; every `measured` array and every
               per-workload row must be equal.
4. headline  — `common.run_sweep` at full scale (parity config: 8 CPUs and
               a GPU on 2 channels, 86-entry buffer; 105 mixes plus the
               alone baselines; 16k+2k cycles), strict and forced into a
               throwaway results cache, once cold and once warm. It must
               compile 1 stacked program plus one per non-stackable policy
               (sms, sms_dash), and the warm call must repeat the cold
               call's results exactly.

Every printed number is labelled with the device it ran on. The last line
of stdout, printed only when every phase passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Nothing here imports `repro.launch`: its dry-run modules overwrite
XLA_FLAGS when imported and must stay off the chip path.
"""
from __future__ import annotations

import dataclasses
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import common  # noqa: E402
from repro import compat, compile_cache  # noqa: E402
from repro.core import golden  # noqa: E402
from repro.core import simulator as sim  # noqa: E402
from repro.core import workloads as wl  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Scale:
    n_per_cat: int          # headline mixes per category (7 categories)
    n_cycles: int           # headline measured cycles
    warmup: int
    ref_n_per_cat: int      # chip-vs-CPU reference sweep
    ref_cycles: int
    ref_warmup: int


# the headline is `benchmarks/simspeed.SWEEP_SCALE`
FULL = Scale(n_per_cat=15, n_cycles=16_000, warmup=2_000,
             ref_n_per_cat=1, ref_cycles=2_000, ref_warmup=500)


class PhaseFailed(RuntimeError):
    pass


def _say(label: str, msg: str) -> None:
    print(f"[{label}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# phase 2: golden digests
# ---------------------------------------------------------------------------

def _digest_diff(what: str, a, b):
    """Keys whose digests differ between two (src, sched, dram) states."""
    bad = []
    for part, x, y in zip(("src", "sched", "dram"), a, b):
        dx, dy = golden.digest(x), golden.digest(y)
        if set(dx) != set(dy):
            bad.append(f"{what} {part} keys differ: "
                       f"{sorted(set(dx) ^ set(dy))}")
        bad += [f"{what} {part}[{k}] diverged"
                for k in sorted(set(dx) & set(dy)) if dx[k] != dy[k]]
    return bad


def _stacked_run(n_cycles: int, device):
    """{policy: final state} of one stacked run of the whole family."""
    cfg = golden.CFG
    with jax.default_device(device):
        return sim.simulate_debug_stacked(
            cfg, sim.stackable_names(cfg), golden.pool(),
            np.ones(cfg.n_src, bool), n_cycles=n_cycles)


def _debug_run(name: str, n_cycles: int, stacked: bool, device):
    if stacked:
        return _stacked_run(n_cycles, device)[name]
    cfg = golden.CFG
    with jax.default_device(device):
        return sim.simulate_debug(cfg, name, golden.pool(),
                                  np.ones(cfg.n_src, bool),
                                  n_cycles=n_cycles)


def first_divergence(name: str, stacked: bool, chip, cpu, label: str):
    """Bisect the cycle count at which chip and CPU final states part, and
    print the diverging keys with both sides' values at that cycle."""
    same = lambda n: not _digest_diff(
        name, _debug_run(name, n, stacked, chip),
        _debug_run(name, n, stacked, cpu))
    lo, hi = 0, golden.N_CYCLES
    if same(hi):
        _say(label, f"{name}: chip and CPU agree at {hi} cycles; the "
             f"mismatch is against the golden file, not the CPU")
        return
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if same(mid) else (lo, mid)
    a = _debug_run(name, hi, stacked, chip)
    b = _debug_run(name, hi, stacked, cpu)
    _say(label, f"{name} ({'stacked' if stacked else 'solo'}): chip and CPU "
         f"first part after {hi} cycles (cycle index {hi - 1})")
    for part, x, y in zip(("src", "sched", "dram"), a, b):
        for k in sorted(x):
            if k in y and not np.array_equal(x[k], y[k]):
                _say(label, f"  {part}[{k}] chip={np.asarray(x[k]).tolist()}"
                     f" cpu={np.asarray(y[k]).tolist()}")


def golden_phase(chip, cpu, label: str) -> None:
    gold = golden.load()
    cfg, N = golden.CFG, golden.N_CYCLES
    bad, diverged = [], []
    for name in sorted(gold):
        b = golden.compare(name, _debug_run(name, N, False, chip), gold[name])
        bad += b
        diverged += [(name, False)] if b else []
    fam = sim.stackable_names(cfg)
    stacked = _stacked_run(N, chip)
    for name in fam:
        got = stacked[name]
        if name in gold:
            b = golden.compare(name, got, gold[name])
        else:
            solo = _debug_run(name, N, False, chip)
            b = _digest_diff(f"{name} stacked-vs-solo", got, solo)
            if cpu is not None:
                b += _digest_diff(f"{name} chip-vs-cpu", solo,
                                  _debug_run(name, N, False, cpu))
        bad += b
        diverged += [(name, True)] if b else []
    n_checked = len(gold) + len(fam)
    if bad:
        for line in bad:
            _say(label, f"golden mismatch: {line}")
        if cpu is not None:
            for name, stacked in diverged:
                first_divergence(name, stacked, chip, cpu, label)
        raise PhaseFailed(f"golden digests: {len(bad)} mismatches")
    _say(label, f"golden digests: {len(gold)} solo policies and "
         f"{len(fam)} stacked slices match ({n_checked} final states, "
         f"{N} cycles each)")


# ---------------------------------------------------------------------------
# phase 3: chip against CPU on the sweep path
# ---------------------------------------------------------------------------

def _sweep(cfg, policies, wls, n_cycles, warmup, tag):
    with common.throwaway_cache(prefix="chip_smoke_"):
        res = common.run_sweep(cfg, policies, wls, n_cycles=n_cycles,
                               warmup=warmup, tag=tag, force=True,
                               strict=True)
    errors = [p for p, r in res.items() if "error" in r]
    if errors:
        raise PhaseFailed(f"sweep returned error entries for {errors}")
    return res


def _same_results(a, b):
    """Names of the `measured` arrays and per-workload row metrics that
    differ between two run_sweep results."""
    bad = []
    for pol in a:
        ma, mb = a[pol]["measured"], b[pol]["measured"]
        if set(ma) != set(mb):
            bad.append(f"{pol}: measured keys {sorted(set(ma) ^ set(mb))}")
        bad += [f"{pol}:measured.{k}" for k in sorted(set(ma) & set(mb))
                if not np.array_equal(np.asarray(ma[k]), np.asarray(mb[k]),
                                      equal_nan=True)]
        ra, rb = a[pol]["rows"], b[pol]["rows"]
        bad += [f"{pol}:rows[{i}].{k}" for i, (x, y) in enumerate(zip(ra, rb))
                for k in x if not np.array_equal(x[k], y.get(k),
                                                 equal_nan=True)]
    return bad


def reference_phase(scale: Scale, cpu, label: str) -> None:
    cfg = common.parity_config()
    pols = list(sim.ALL_POLICIES)
    wls = wl.make_workloads(cfg.n_cpu, n_per_cat=scale.ref_n_per_cat)
    args = (cfg, pols, wls, scale.ref_cycles, scale.ref_warmup,
            "chip_smoke_ref")
    chip_res = _sweep(*args)
    with jax.default_device(cpu):
        cpu_res = _sweep(*args)
    bad = _same_results(chip_res, cpu_res)
    if bad:
        raise PhaseFailed(f"chip and CPU sweeps differ: {bad[:20]}")
    n_arr = sum(len(r["measured"]) for r in chip_res.values())
    _say(label, f"reference sweep: {len(pols)} policies x {len(wls)} mixes, "
         f"{scale.ref_cycles}+{scale.ref_warmup} cycles — all {n_arr} "
         f"measured arrays equal on chip and CPU")


# ---------------------------------------------------------------------------
# phase 4: the headline sweep
# ---------------------------------------------------------------------------

def _programs():
    return (compat.jit_cache_size(sim._sim_batch_stacked),
            compat.jit_cache_size(sim._sim_batch))


def headline_phase(scale: Scale, label: str) -> None:
    cfg = common.parity_config()
    pols = list(sim.ALL_POLICIES)
    wls = wl.make_workloads(cfg.n_cpu, n_per_cat=scale.n_per_cat)
    n_alone = len(wl.alone_batch(cfg)[2])
    n_fallback = len(pols) - len(sim.stackable_names(cfg, pols))
    if scale.n_cycles < FULL.n_cycles:
        _say(label, f"headline n_cycles cut from {FULL.n_cycles} to "
             f"{scale.n_cycles}")
    _say(label, f"headline sweep: {len(pols)} policies x ({len(wls)} mixes "
         f"+ {n_alone} alone rows), {cfg.n_cpu} CPUs + GPU, "
         f"{cfg.n_channels} channels, {cfg.buf_entries}-entry buffer, "
         f"{scale.n_cycles}+{scale.warmup} cycles, strict, forced")
    args = (cfg, pols, wls, scale.n_cycles, scale.warmup, "chip_smoke")
    p0 = _programs()
    t0 = time.perf_counter()
    with compile_cache.counting() as cache:
        first = _sweep(*args)
    t_first = time.perf_counter() - t0
    p1 = _programs()
    t0 = time.perf_counter()
    warm = _sweep(*args)
    t_warm = time.perf_counter() - t0
    p2 = _programs()

    stacked, per_policy = p1[0] - p0[0], p1[1] - p0[1]
    cycw = (scale.n_cycles + scale.warmup) * (len(wls) + n_alone) * len(pols)
    _say(label, f"headline first call (trace+compile+run): {t_first:.3f} s "
         f"(persistent compile cache: {cache['hits']} hits, "
         f"{cache['misses']} misses)")
    _say(label, f"headline warm call (run only): {t_warm:.3f} s")
    _say(label, f"xla programs: stacked={stacked} per_policy={per_policy} "
         f"(warm call added {p2[0] - p1[0]}+{p2[1] - p1[1]})")
    _say(label, f"simulated cycle-workloads/s: first={cycw / t_first:.1f} "
         f"warm={cycw / t_warm:.1f} ({cycw} cycle-workloads per call)")
    agg = {p: first[p]["agg"] for p in pols}
    best = max(sim.stackable_names(cfg, pols),
               key=lambda p: agg[p]["weighted_speedup"])
    for tag, p in (("sms", "sms"), (f"best centralized ({best})", best)):
        _say(label, f"{tag}: weighted_speedup="
             f"{agg[p]['weighted_speedup']:.6f} "
             f"max_slowdown={agg[p]['max_slowdown']:.6f}")

    bad = []
    if (stacked, per_policy) != (1, n_fallback):
        bad.append(f"expected 1 stacked + {n_fallback} per-policy programs, "
                   f"got {stacked} + {per_policy}")
    if p2 != p1:
        bad.append(f"warm call compiled again: {p1} -> {p2}")
    for p in pols:
        ws, sd = agg[p]["weighted_speedup"], agg[p]["max_slowdown"]
        if not (math.isfinite(ws) and math.isfinite(sd) and ws > 0
                and sd > 0):
            bad.append(f"{p}: weighted_speedup={ws} max_slowdown={sd}")
    bad += [f"warm differs: {b}" for b in _same_results(first, warm)[:20]]
    if bad:
        raise PhaseFailed(f"headline sweep: {bad}")


# ---------------------------------------------------------------------------

def main(scale: Scale = FULL, platform: str = "tpu") -> int:
    """Run every phase; returns the exit code. `platform` and `scale` are
    overridden only by the CPU rehearsal test."""
    cache_dir = compile_cache.enable()
    devs = jax.devices()
    dev = devs[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    label = f"{dev.platform} {dev.device_kind}"
    _say(label, f"devices: platform={info['platform']} kind={info['kind']} "
         f"count={info['count']} jax={jax.__version__}")
    n_cached = len(list(Path(cache_dir).glob("*"))) \
        if Path(cache_dir).is_dir() else 0
    _say(label, f"compile cache: {cache_dir} ({n_cached} entries at start)")
    if dev.platform != platform:
        print(f"chip_smoke: needs a {platform} device, JAX found "
              f"{dev.platform}; no fallback", file=sys.stderr)
        return 2
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:
        cpu = None
        _say(label, "no CPU device visible: chip-vs-CPU checks skipped")
    phases = [("golden", lambda: golden_phase(dev, cpu, label))]
    if cpu is not None:
        phases.append(("reference",
                       lambda: reference_phase(scale, cpu, label)))
    phases.append(("headline", lambda: headline_phase(scale, label)))
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except PhaseFailed as e:
            print(f"chip_smoke: phase {name} failed: {e}", file=sys.stderr)
            return 1
        _say(label, f"phase {name} passed in "
             f"{time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
