"""Train an LM (reduced config of any assigned arch) with checkpoints.

Default trains a ~10M-param yi-family model for 300 steps on the synthetic
stream, checkpointing every 100; rerunning the same command auto-resumes.

  PYTHONPATH=src python examples/train_lm.py --arch yi-9b --steps 300
  PYTHONPATH=src python examples/train_lm.py --arch moonshot-v1-16b-a3b
"""
import argparse

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCH_IDS, get_config
from repro.launch.train import RunConfig, train_loop
from repro.train.data import DataConfig
from repro.train.optimizer import OptimizerConfig


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="yi-9b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    n_params = cfg.param_count()
    print(f"training {cfg.name} ({cfg.family}), ~{n_params / 1e6:.1f}M "
          f"params, {args.steps} steps")
    out = train_loop(
        cfg,
        DataConfig(batch_size=args.batch, seq_len=args.seq,
                   vocab_size=cfg.vocab_size),
        OptimizerConfig(peak_lr=1e-3, warmup_steps=20,
                        total_steps=args.steps),
        RunConfig(steps=args.steps, ckpt_every=100,
                  ckpt_dir=args.ckpt_dir, log_every=20))
    print(f"final loss: {out['final_loss']:.4f} "
          f"(start {out['history'][0]:.4f})")


if __name__ == "__main__":
    main()
