"""Serve a small LM: batched prefill + greedy decode with a KV cache.

  PYTHONPATH=src python examples/serve_lm.py --arch minicpm3-4b --new 48
(minicpm3 exercises the MLA latent cache + absorbed decode.)
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCH_IDS, get_config
from repro.models import model as M
from repro.train.serve_step import greedy_generate


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="minicpm3-4b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new", type=int, default=32)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    if cfg.family not in ("dense", "moe"):
        raise SystemExit(f"{args.arch}: serve example targets decoder-only "
                         "LMs (dense/moe)")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompt = (jnp.arange(args.batch * args.prompt_len, dtype=jnp.int32)
              .reshape(args.batch, args.prompt_len) * 17) % cfg.vocab_size

    max_seq = args.prompt_len + args.new
    t0 = time.time()
    out = greedy_generate(params, prompt, cfg, max_new=args.new,
                          max_seq=max_seq)
    dt = time.time() - t0
    print(f"{cfg.name}: batch={args.batch} prompt={args.prompt_len} "
          f"new={args.new}")
    print(f"generated shape {out.shape} in {dt:.2f}s "
          f"({args.batch * args.new / dt:.1f} tok/s incl. compile)")
    for b in range(min(args.batch, 2)):
        print(f"  seq{b}: {out[b, :12].tolist()} ...")


if __name__ == "__main__":
    main()
