"""Training launcher: real steps on the local device(s), checkpoint/restart.

  PYTHONPATH=src python -m repro.launch.train --arch gemma3-4b --smoke \
      --steps 200 --ckpt-dir /tmp/ckpt --ckpt-every 50

Fault tolerance: checkpoints are step-atomic; rerunning the same command
resumes from the latest complete checkpoint (data pipeline included — batches
are a pure function of (seed, step)).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.models import registry
from repro.training import checkpoint as ckpt
from repro.training.data import DataConfig, jax_batch_at
from repro.training.optimizer import AdamWConfig, adamw_init
from repro.training.train_step import TrainConfig, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    tcfg = TrainConfig(adamw=AdamWConfig(lr=args.lr, warmup_steps=args.warmup,
                                         total_steps=args.steps))
    step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                    global_batch=args.batch)

    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = adamw_init(params)
    start = 0
    if args.ckpt_dir:
        restored = ckpt.restore_latest(args.ckpt_dir, {"p": params, "o": opt_state})
        if restored:
            start, tree, extra = restored
            params, opt_state = tree["p"], tree["o"]
            print(f"resumed from step {start}")

    extras = {}
    if cfg.family == "vlm":
        extras["patch_embeds"] = jnp.zeros((args.batch, cfg.num_patches, cfg.d_model), jnp.bfloat16)
    if cfg.family == "encdec":
        extras["enc_embeds"] = jnp.zeros((args.batch, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)

    t0 = time.time()
    for step in range(start, args.steps):
        batch = jax_batch_at(dc, step, extras)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if (step + 1) % args.log_every == 0:
            print(f"step {step+1:5d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"({(time.time()-t0)/(step-start+1):.2f}s/step)", flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, step + 1, {"p": params, "o": opt_state},
                      extra={"arch": args.arch})
    print(f"done: {args.steps - start} steps in {time.time()-t0:.1f}s")
    return params


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
