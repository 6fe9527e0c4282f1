"""Training launcher: builds model/optimizer/data from an arch config and
runs the fault-tolerant loop. On the production mesh this is the entry point
a scheduler (re)starts on every elastic event; on CPU it drives the reduced
configs for the examples.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --reduced --steps 200 --batch 8 --seq 64
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_arch, reduced as make_reduced
from repro.data import DataConfig, SyntheticTokens
from repro.launch.compile_cache import setup_compile_cache
from repro.models import build_model
from repro.optim import AdamW, cosine_with_warmup
from repro.train import LoopConfig, TrainLoop


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compression", default="none",
                    choices=["none", "bf16", "int8ef"])
    ap.add_argument("--fail-at", type=int, default=None)
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    model = build_model(cfg, compute_dtype=jnp.float32, remat="none")
    opt = AdamW(lr=cosine_with_warmup(args.lr, args.steps // 10, args.steps))
    data = SyntheticTokens(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch))
    loop = TrainLoop(model, opt, data, LoopConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir, compression=args.compression),
        fail_at_step=args.fail_at)
    out = loop.run()
    h = out["history"]
    print(f"steps {h[0]['step']}..{h[-1]['step']}  "
          f"loss {h[0]['loss']:.4f} -> {h[-1]['loss']:.4f}  "
          f"stragglers={len(out['stragglers'])}")


if __name__ == "__main__":
    main()
