"""Distribution: sharding-rule legality for every arch, multi-device pjit
end-to-end (subprocess with forced host devices), GPipe, elastic restore,
and dry-run artifact validation."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.base import SHAPES, shape_cells
from repro.configs.registry import ARCH_IDS, get_config

REPO = Path(__file__).resolve().parents[1]


def _run_sub(code: str, devices: int = 8, timeout: int = 540) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, f"stdout:{r.stdout}\nstderr:{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_sharding_rules_legal(arch):
    """Every param's PartitionSpec divides its shape on the 16x16 mesh."""
    from jax.sharding import PartitionSpec
    from repro.distributed import sharding as shlib
    from repro.models.registry import get_model

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
        devices = np.empty((16, 16), object)

    cfg = get_config(arch)
    bundle = get_model(cfg)
    rules = shlib.axis_rules(cfg, FakeMesh())
    axes_tree = bundle.axes()
    abstract = bundle.abstract_params()
    leaves_ax = jax.tree_util.tree_leaves(
        axes_tree, is_leaf=lambda x: isinstance(x, tuple) and
        all(isinstance(e, (str, type(None))) for e in x))
    leaves_ab = jax.tree_util.tree_leaves(abstract)
    assert len(leaves_ax) == len(leaves_ab)
    for axes, av in zip(leaves_ax, leaves_ab):
        assert len(axes) == len(av.shape), f"{arch}: {axes} vs {av.shape}"
        used = set()
        for ax_name, dim in zip(axes, av.shape):
            m = rules.get(ax_name)
            if m is None or m in used:
                continue
            used.add(m)
            assert dim % 16 == 0, \
                f"{arch}: axis {ax_name} dim {dim} not divisible by 16"


def test_pjit_train_step_multidevice():
    """Real 2x4 mesh end-to-end train step (8 host devices, subprocess)."""
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.configs.base import reduced, RunConfig, ShapeConfig
        from repro.configs.registry import get_config
        from repro.models.registry import get_model
        from repro.train import steps as steps_lib
        from repro.optim import adamw
        from repro.data.pipeline import DataConfig, synthetic_batch
        mesh = compat.make_mesh((2, 4), ("data", "model"))
        cfg = reduced(get_config("qwen1.5-4b"), n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128,
                      vocab_size=256)
        run = RunConfig(compute_dtype="float32", remat="full", lr=1e-3)
        shape = ShapeConfig("t", "train", 32, 8)
        with mesh:
            step, in_sh = steps_lib.build_train_step(cfg, run, mesh, shape)
            bundle = get_model(cfg)
            params = bundle.init(jax.random.PRNGKey(0))
            opt = adamw.init(params)
            dc = DataConfig(cfg.vocab_size, shape.seq_len, shape.global_batch)
            jstep = jax.jit(step, in_shardings=in_sh)
            losses = []
            for s in range(4):
                b = {k: jnp.asarray(v) for k, v in
                     synthetic_batch(dc, s).items()}
                params, opt, _, m = jstep(params, opt, jnp.zeros(()), b,
                                          jnp.int32(s))
                losses.append(float(m["loss"]))
            assert all(np.isfinite(losses)), losses
            assert losses[-1] < losses[0], losses
            print("LOSSES", [round(l, 3) for l in losses])
    """)
    assert "LOSSES" in out


def test_moe_ep_multidevice_matches_single():
    """shard_map EP on a 4-way model mesh == single-device reference."""
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import compat
        from repro.configs.base import reduced, RunConfig
        from repro.configs.registry import get_config
        from repro.models import moe as moe_lib
        cfg = reduced(get_config("moonshot-v1-16b-a3b"), n_experts=8)
        run = RunConfig(compute_dtype="float32")
        rng = np.random.RandomState(0)
        p = {k: jnp.asarray(rng.randn(*d.shape) * 0.05, jnp.float32)
             for k, d in moe_lib.moe_defs(cfg).items()}
        x = jnp.asarray(rng.randn(4, 8, cfg.d_model), jnp.float32)
        ref, aux_ref = moe_lib.moe_apply(x, p, cfg, run, mesh=None)
        mesh = compat.make_mesh((2, 4), ("data", "model"))
        with mesh:
            f = jax.jit(lambda x, p: moe_lib.moe_apply(
                x, p, cfg, run, mesh=mesh, batch_axes=("data",)))
            y, aux = f(x, p)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(y),
                                   atol=2e-4, rtol=2e-3)
        # aux is mean-of-per-DP-shard losses vs the reference's global-batch
        # loss: same scale, not bitwise equal
        assert abs(float(aux_ref) - float(aux)) / float(aux_ref) < 0.2
        print("MOE_EP_OK")
    """)
    assert "MOE_EP_OK" in out


def test_gpipe_multidevice():
    out = _run_sub("""
        import jax, jax.numpy as jnp, functools
        from repro import compat
        from repro.distributed.pipeline import gpipe_apply
        mesh = compat.make_mesh((4,), ("pod",))
        W = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 16)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
        fn = lambda w, h: jnp.tanh(h @ w)
        out = gpipe_apply(fn, W, x, n_micro=4, mesh=mesh)
        ref = functools.reduce(lambda h, i: jnp.tanh(h @ W[i]), range(4), x)
        assert float(jnp.abs(out - ref).max()) < 1e-5
        g = jax.grad(lambda W: gpipe_apply(fn, W, x, 4, mesh).sum())(W)
        gr = jax.grad(lambda W: functools.reduce(
            lambda h, i: jnp.tanh(h @ W[i]), range(4), x).sum())(W)
        assert float(jnp.abs(g - gr).max()) < 1e-4
        print("GPIPE_OK")
    """, devices=4)
    assert "GPIPE_OK" in out


def test_elastic_restore_across_meshes():
    """Save under a 4-device mesh, restore+train under 2 devices."""
    out = _run_sub("""
        import jax, jax.numpy as jnp, tempfile, subprocess, sys, os, textwrap
        from repro import compat
        from repro.configs.base import reduced, RunConfig, ShapeConfig
        from repro.configs.registry import get_config
        from repro.launch.mesh import make_local_mesh
        from repro.train.trainer import Trainer
        d = tempfile.mkdtemp()
        cfg = reduced(get_config("qwen1.5-4b"), n_layers=2)
        run = RunConfig(compute_dtype="float32", remat="none", lr=1e-3)
        shape = ShapeConfig("t", "train", 32, 8)
        mesh = compat.make_mesh((4, 1), ("data", "model"))
        tr = Trainer(cfg, run, mesh, shape, ckpt_dir=d, ckpt_every=2)
        with mesh:
            tr.train(2)
        print("SAVED_DIR", d)
    """, devices=4)
    d = out.split("SAVED_DIR")[1].strip()
    out2 = _run_sub(f"""
        import jax
        from repro import compat
        from repro.configs.base import reduced, RunConfig, ShapeConfig
        from repro.configs.registry import get_config
        from repro.train.trainer import Trainer
        cfg = reduced(get_config("qwen1.5-4b"), n_layers=2)
        run = RunConfig(compute_dtype="float32", remat="none", lr=1e-3)
        shape = ShapeConfig("t", "train", 32, 8)
        mesh = compat.make_mesh((2, 1), ("data", "model"))
        tr = Trainer(cfg, run, mesh, shape, ckpt_dir={d!r}, ckpt_every=10)
        st = tr.maybe_restore()
        assert st is not None and st.step == 2, st
        with mesh:
            st = tr.train(2, state=st)
        assert st.step == 4
        print("ELASTIC_OK")
    """, devices=2)
    assert "ELASTIC_OK" in out2


def test_perf_knobs_preserve_semantics():
    """attn_pad_heads / attn_batch_reshard / decode knobs are pure layout
    optimizations: losses and decode logits must match the baseline."""
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro import compat
        from repro.configs.base import reduced, RunConfig, ShapeConfig
        from repro.configs.registry import get_config
        from repro.models.registry import get_model
        from repro.models import lm as lm_lib
        mesh = compat.make_mesh((2, 4), ("data", "model"))
        # 3 heads don't divide model=4 -> pad/reshard paths exercised
        cfg = reduced(get_config("gemma2-2b"), n_layers=2, d_model=48,
                      n_heads=3, n_kv_heads=1, head_dim=16, d_ff=96,
                      vocab_size=128)
        bundle = get_model(cfg)
        params = bundle.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 128)
        batch = {"tokens": toks, "labels": toks}
        base_run = RunConfig(compute_dtype="float32", remat="none")
        with mesh:
            ref = float(jax.jit(lambda p, b: bundle.train_loss(
                p, base_run, b, mesh=mesh))(params, batch))
            for knob in ("attn_pad_heads", "attn_batch_reshard"):
                run = dataclasses.replace(base_run, **{knob: True})
                got = float(jax.jit(lambda p, b: bundle.train_loss(
                    p, run, b, mesh=mesh))(params, batch))
                assert abs(got - ref) < 1e-4, (knob, got, ref)
        # decode knobs (single device path is fine for numerics)
        cache = bundle.init_cache(8, 16, dtype=jnp.float32)
        lg_ref, c2, lens = bundle.prefill(params, base_run, cache,
                                          toks[:, :15])
        d_ref, _ = bundle.decode_step(params, base_run, c2, toks[:, 15], lens)
        run = dataclasses.replace(base_run, decode_grouped=True,
                                  decode_slim_mask=True)
        d_opt, _ = bundle.decode_step(params, run, c2, toks[:, 15], lens)
        np.testing.assert_allclose(np.asarray(d_ref), np.asarray(d_opt),
                                   atol=1e-5, rtol=1e-5)
        print("KNOBS_OK")
    """)
    assert "KNOBS_OK" in out


# ---------------------------------------------------------------------------
# dry-run artifacts (deliverable e)
# ---------------------------------------------------------------------------

DRYRUN = REPO / "experiments" / "dryrun"


@pytest.mark.skipif(not DRYRUN.exists(), reason="dry-run not yet generated")
def test_dryrun_all_cells_present_and_clean():
    expected = []
    for arch in ARCH_IDS:
        for cell in shape_cells(arch):
            for mesh in ("single_pod", "multi_pod"):
                expected.append(f"{arch}__{cell}__{mesh}.json")
    missing, errors = [], []
    for name in expected:
        p = DRYRUN / name
        if not p.exists():
            missing.append(name)
            continue
        rec = json.loads(p.read_text())
        if "error" in rec:
            errors.append(name)
    assert not missing, f"missing dry-run cells: {missing}"
    assert not errors, f"failed dry-run cells: {errors}"


@pytest.mark.skipif(not DRYRUN.exists(), reason="dry-run not yet generated")
def test_dryrun_records_have_roofline_terms():
    for p in DRYRUN.glob("*__single_pod.json"):
        rec = json.loads(p.read_text())
        if "error" in rec:
            continue
        r = rec["roofline"]
        assert r["compute_s"] >= 0 and r["memory_s"] > 0
        assert r["bottleneck"] in ("compute", "memory", "collective")
        assert rec["cost_analysis"].get("flops", 0) > 0
