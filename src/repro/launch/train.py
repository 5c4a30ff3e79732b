"""Training launcher: small-scale runnable entry point.

On this CPU container it trains reduced/~100M-class configs end to end
(see examples/train_lm.py); on a real pod the same code path jits the
train step with the production mesh shardings from launch.dryrun.

Usage:
  python -m repro.launch.train --arch granite-3-2b --reduced --steps 200
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro.configs.base import ParallelConfig
from repro.configs.registry import ARCH_IDS, get_config
from repro.data.pipeline import SyntheticTokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model_zoo import build_model
from repro.optim import OptimizerConfig, optimizer_init, warmup_cosine
from repro.train import Trainer, TrainerConfig, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    parallel = ParallelConfig(remat="none", compute_dtype="float32")
    model = build_model(cfg, parallel)
    print(f"{cfg.name}: {model.n_params:,} params")

    opt_cfg = OptimizerConfig(kind="adamw", lr=args.lr)
    sched = warmup_cosine(args.lr, warmup=max(args.steps // 20, 1), total=args.steps)
    step_fn = jax.jit(make_train_step(model, opt_cfg, parallel, sched))

    pipeline = SyntheticTokenPipeline(
        cfg.vocab_size, args.seq, args.batch, seed=args.seed
    )

    def wrapped_step(params, opt_state, batch, step):
        if cfg.family == "vlm":
            b = batch["tokens"].shape[0]
            batch = dict(batch)
            batch["vision_embeds"] = jnp.zeros(
                (b, cfg.vision_tokens, cfg.d_model), jnp.float32
            )
            batch["labels"] = jnp.concatenate(
                [jnp.full((b, cfg.vision_tokens), -1, jnp.int32), batch["labels"]],
                axis=1,
            )
        if cfg.family == "audio":
            b = batch["tokens"].shape[0]
            batch = dict(batch)
            batch["frames"] = jnp.zeros(
                (b, cfg.encoder_len, cfg.d_model), jnp.float32
            )
        return step_fn(params, opt_state, batch, step)

    trainer = Trainer(
        wrapped_step,
        pipeline,
        TrainerConfig(
            total_steps=args.steps,
            ckpt_every=args.ckpt_every,
            ckpt_dir=args.ckpt_dir,
        ),
        init_params=lambda: model.init(jax.random.PRNGKey(args.seed)),
        init_opt_state=lambda p: optimizer_init(opt_cfg, p),
    )
    out = trainer.run()
    print(
        json.dumps(
            {
                "final_step": out["final_step"],
                "final_loss": out["final_loss"],
                "mean_step_time": out["mean_step_time"],
            }
        )
    )


if __name__ == "__main__":
    main()
