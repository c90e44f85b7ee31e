"""End-to-end training driver: ~100M-param xLSTM for a few hundred steps.

  PYTHONPATH=src python examples/train_lm.py --arch xlstm-125m --steps 300
  PYTHONPATH=src python examples/train_lm.py --smoke        # tiny + fast

Demonstrates: deterministic sharded data, AdamW + cosine schedule, remat,
async atomic checkpointing with resume, straggler detection. On this CPU
container the full 125M model is slow; --smoke runs a reduced config.
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax

from repro import compile_cache
from repro.configs.base import RunConfig, ShapeConfig, reduced
from repro.configs.registry import get_config
from repro.launch.mesh import make_local_mesh
from repro.train.trainer import StragglerPolicy, Trainer


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
        args.steps = min(args.steps, 30)
        args.seq = 128
    run = RunConfig(seq_len=args.seq, global_batch=args.batch,
                    compute_dtype="float32", remat="none", lr=3e-4,
                    warmup_steps=20, total_steps=args.steps)
    shape = ShapeConfig("train", "train", args.seq, args.batch)
    trainer = Trainer(cfg, run, make_local_mesh(), shape,
                      ckpt_dir=args.ckpt, ckpt_every=50,
                      straggler=StragglerPolicy(action="report"))
    print(f"arch={cfg.name} params={cfg.n_params() / 1e6:.1f}M "
          f"tokens/step={shape.tokens}")
    state = trainer.train(args.steps)
    for m in trainer.metrics_log[:: max(len(trainer.metrics_log) // 10, 1)]:
        print(f"step {m['step']:4d} loss {m['loss']:.4f} "
              f"({m['step_time_s']:.2f}s)")
    print(f"final loss {trainer.metrics_log[-1]['loss']:.4f} "
          f"at step {state.step}")
    if trainer.events:
        print("events:", trainer.events)


if __name__ == "__main__":
    main()
