"""The golden-digest contract: bit-identity of the simulator's final state.

`tests/golden_policy_states.json` holds, per policy, a sha1 per key of the
final raw state (`simulate_debug`: source, scheduler and DRAM trees) after
`N_CYCLES` cycles of the capture config `CFG` on the seed-42 pool. Every
speed change must leave these digests untouched; `compare` is the one
comparison that the tests and the chip smoke run share.

Additive subsystems may add keys on top of the capture: energy and QoS
counters in the DRAM tree, the N-class frame accounting in the source
tree. Scheduler state was slimmed when the policies were ported, so
scheduler keys are compared where both sides have them, and a few
essential ones must be present so the comparison is never vacuous.
"""
from __future__ import annotations

import hashlib
import json
from pathlib import Path
from typing import Dict, Iterable, List

import numpy as np

from repro.core import energy, engine, qos
from repro.core.params import SimConfig

GOLDEN_FILE = Path(__file__).resolve().parents[3] / "tests" / \
    "golden_policy_states.json"

CFG = SimConfig(n_cpu=3, n_gpu=1, n_channels=2, buf_entries=24, fifo_size=5,
                dcs_size=3)
N_CYCLES = 1_500

# keys whose presence proves the scheduler comparison isn't vacuous
ESSENTIAL_SCHED = {
    "sms": ("f_len", "f_row", "d_len", "d_src", "drain_left", "rr_bank"),
    "centralized": ("valid", "src", "bank", "row", "birth", "marked"),
}


def load() -> Dict[str, Dict[str, Dict[str, str]]]:
    """{policy: {"src"|"sched"|"dram": {key: sha1}}}."""
    return json.loads(GOLDEN_FILE.read_text())


def pool(cfg: SimConfig = CFG) -> Dict[str, np.ndarray]:
    """The capture-time source pool (seed 42); must never change."""
    rng = np.random.RandomState(42)
    S = cfg.n_src
    mpki = rng.uniform(2, 40, S).astype(np.float32)
    out = {
        "mpki": mpki,
        "inst_per_miss": np.maximum(1000.0 / mpki, 1.0).astype(np.float32),
        "rbl": rng.uniform(0.1, 0.95, S).astype(np.float32),
        "blp": rng.randint(1, 7, S).astype(np.int32),
        "is_gpu": np.asarray([False] * cfg.n_cpu + [True]),
        "dl_period": np.zeros(S, np.int32),
        "dl_reqs": np.zeros(S, np.int32),
    }
    out["dl_period"][0] = 400
    out["dl_reqs"][0] = 35
    return out


def digest(tree: Dict[str, np.ndarray]) -> Dict[str, str]:
    """Per-key sha1 over dtype, shape and bytes (private `_` keys skipped)."""
    out = {}
    for key in sorted(tree):
        if key.startswith("_"):
            continue
        v = np.ascontiguousarray(tree[key])
        h = hashlib.sha1()
        h.update(str(v.dtype).encode())
        h.update(str(v.shape).encode())
        h.update(v.tobytes())
        out[key] = h.hexdigest()
    return out


def compare(policy_name: str, state, golden: Dict[str, Dict[str, str]],
            extra_dram: Iterable[str] = ()) -> List[str]:
    """Every way `state` (src, sched, dram) departs from `golden`; [] = equal.

    `extra_dram` names further additive DRAM keys the caller switched on
    (e.g. the sanitizer's violation counters).
    """
    st_f, sched_f, dram_f = state
    bad = []
    for part, tree in (("src", st_f), ("dram", dram_f)):
        new = digest(tree)
        allowed = set(energy.STATE_KEYS) | set(qos.STATE_KEYS) \
            | set(extra_dram) if part == "dram" \
            else set(engine.NCLASS_SRC_KEYS)
        drift = set(new) ^ set(golden[part])
        if not drift <= allowed:
            bad.append(f"{policy_name} {part} keys drifted: "
                       f"{sorted(drift - allowed)}")
        bad += [f"{policy_name} {part}[{k}] diverged"
                for k, h in golden[part].items() if new.get(k) != h]
    sched = digest(sched_f)
    essential = ESSENTIAL_SCHED[
        "sms" if policy_name.startswith("sms") else "centralized"]
    bad += [f"{policy_name} missing sched key {k}" for k in essential
            if k not in sched or k not in golden["sched"]]
    bad += [f"{policy_name} sched[{k}] diverged"
            for k in sorted(set(sched) & set(golden["sched"]))
            if sched[k] != golden["sched"][k]]
    return bad
