"""`chip_smoke.py` rehearsed on the CPU at a tiny size.

The chip run itself needs a TPU; here every phase runs end to end on the
host CPU (golden digests at full capture length, the chip-vs-CPU reference
sweep, the headline sweep at a few hundred cycles) through `main`'s
platform and scale overrides, so a broken path fails here before it costs
chip time.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

import chip_smoke

REPO = Path(__file__).resolve().parents[1]
# reference and headline shapes differ, so the headline's program count
# is not masked by the reference sweep's jit cache entries
TINY = chip_smoke.Scale(n_per_cat=1, n_cycles=300, warmup=100,
                        ref_n_per_cat=1, ref_cycles=200, ref_warmup=50)


@pytest.fixture
def cache_in_tmp(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)
    compilation_cache.reset_cache()


def test_chip_smoke_rehearsal_on_cpu(cache_in_tmp, capsys):
    rc = chip_smoke.main(TINY, platform="cpu")
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, "\n".join(out)
    last = json.loads(out[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    text = "\n".join(out)
    for phase in ("golden", "reference", "headline"):
        assert f"phase {phase} passed" in text
    assert "xla programs: stacked=1 per_policy=2" in text
    # every number is labelled with the device that produced it
    assert all(line.startswith("[cpu ") for line in out[:-1])


def test_chip_smoke_refuses_a_host_without_the_chip(cache_in_tmp, capsys):
    rc = chip_smoke.main(TINY)              # default platform: tpu
    out = capsys.readouterr().out
    assert rc == 2
    assert '"ok"' not in out


def test_chip_smoke_path_leaves_xla_flags_alone():
    """The launch dry-run modules overwrite XLA_FLAGS when imported; the
    chip path must not import them."""
    code = ("import os, sys, chip_smoke; "
            "print(sorted(m for m in sys.modules "
            "if m.startswith('repro.launch'))); "
            "print(os.environ.get('XLA_FLAGS'))")
    env = dict(os.environ, XLA_FLAGS="--xla_dump_to=/nonexistent-marker")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    mods, flags = r.stdout.strip().splitlines()
    assert mods == "[]"
    assert flags == "--xla_dump_to=/nonexistent-marker"
