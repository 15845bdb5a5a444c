"""Training launcher.

Single-host CPU runs use real arrays on the default device; pass
``--mesh debug`` to exercise the sharded path on host devices (the
production 16x16 / 2x16x16 meshes are exercised via dryrun.py).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
        --steps 200 --batch 16 --seq 64 --out ckpt/draft
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import init_params, param_count
from repro.train import checkpoint
from repro.train.optimizer import AdamWConfig, init_state
from repro.train.trainer import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke variant of the arch")
    ap.add_argument("--draft-scale", type=int, default=0,
                    help="use draft_variant(arch, scale) instead")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=0,
                    help="override vocab (synthetic data size)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--log-every", type=int, default=20)
    args = ap.parse_args()

    cfg = configs.get_config(args.arch)
    if args.smoke:
        cfg = configs.smoke_variant(cfg)
    if args.draft_scale:
        cfg = configs.draft_variant(cfg, args.draft_scale)
    if args.vocab:
        import dataclasses
        cfg = dataclasses.replace(cfg, vocab=args.vocab)

    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  batch=args.batch, seed=1234))
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    print(f"[train] {cfg.name}: {param_count(params)/1e6:.1f}M params, "
          f"{args.steps} steps x (B={args.batch}, S={args.seq})")
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=min(100, args.steps // 10
                                                       + 1),
                          total_steps=args.steps)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg,
                                      microbatches=args.microbatches))
    opt_state = init_state(params)
    hist = []
    t0 = time.time()
    for i, b in enumerate(data.batches(args.steps)):
        batch = {"tokens": jnp.asarray(b["tokens"])}
        if cfg.n_encoder_layers:
            batch["enc_embeds"] = jax.random.normal(
                jax.random.PRNGKey(i), (args.batch, 32, cfg.d_model)) * .02
        params, opt_state, m = step_fn(params, opt_state, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            m = {k: float(v) for k, v in m.items()}
            hist.append({"step": i, **m})
            print(f"  step {i:5d} loss={m['loss']:.4f} "
                  f"acc={m['accuracy']:.3f} lr={m['lr']:.2e} "
                  f"({time.time()-t0:.0f}s)", flush=True)
    if args.out:
        checkpoint.save(args.out, params,
                        meta={"arch": cfg.name, "smoke": args.smoke,
                              "draft_scale": args.draft_scale,
                              "vocab": cfg.vocab, "steps": args.steps,
                              "history": hist})
        print(f"[train] saved -> {args.out}.npz")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
