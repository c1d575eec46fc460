"""End-to-end training driver.

Two-stage recipe per the paper (§3.2): Stage-1 standard CE training, then
Stage-2 Gatekeeper confidence tuning, with checkpoints after each stage.

CPU-scale examples:
    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --stage1-steps 200 --stage2-steps 100 --alpha 0.3
    PYTHONPATH=src python -m repro.launch.train --preset 100m \
        --stage1-steps 300           # ~100M-param decoder on lm_stream

On a real cluster the same entry point runs full configs under
make_production_mesh() (the dry-run proves those lower; this container is
CPU-only so full-scale execution is out of scope).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig, get_config, reduced
from repro.core.gatekeeper import GatekeeperConfig
from repro.data.pipeline import BatchIterator
from repro.data.synthetic import make_lm_stream, make_qa
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_train_step
from repro.models import transformer as tfm
from repro.sharding import ParallelContext
from repro.training import checkpoint, optim


PRESET_100M = ModelConfig(
    name="repro-100m", family="dense", n_layers=12, d_model=768,
    n_heads=12, n_kv_heads=12, head_dim=64, d_ff=3072, vocab_size=8192,
    qkv_bias=False, rope_theta=10000.0, tie_embeddings=True,
    source="paper-scale driver (~100M params)")


def build_cfg(args) -> ModelConfig:
    if args.preset == "100m":
        return PRESET_100M
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    return cfg


def make_data(cfg: ModelConfig, args, key):
    if args.task == "qa":
        qa = make_qa(key, args.n_train, n_symbols=min(cfg.vocab_size - 16, 16))
        return {"tokens": qa.inputs, "targets": qa.targets,
                "loss_mask": qa.loss_mask}
    stream = make_lm_stream(key, args.n_train, args.seq_len + 1,
                            cfg.vocab_size)
    return {"tokens": stream[:, :-1], "targets": stream[:, 1:]}


def run(args):
    key = jax.random.PRNGKey(args.seed)
    cfg = build_cfg(args)
    ctx = ParallelContext()
    print(f"config: {cfg.name} ({cfg.family}), vocab={cfg.vocab_size}, "
          f"d_model={cfg.d_model}, layers={cfg.n_layers}")
    params = tfm.init_params(cfg, key)
    from repro.sharding import param_count
    print(f"params: {param_count(params)/1e6:.1f}M")

    data = make_data(cfg, args, jax.random.fold_in(key, 1))
    it = BatchIterator(data, args.batch, key=jax.random.fold_in(key, 2))

    for stage, steps, gk_alpha in (
            (1, args.stage1_steps, None),
            (2, args.stage2_steps, args.alpha)):
        if steps <= 0:
            continue
        opt_cfg = optim.AdamWConfig(lr=args.lr if stage == 1 else args.lr * 0.3,
                                    warmup_steps=min(50, steps // 5),
                                    total_steps=steps)
        gk = GatekeeperConfig(alpha=gk_alpha) if gk_alpha is not None else \
            GatekeeperConfig(alpha=1.0)   # alpha=1 + all-correct ≈ CE? no:
        # Stage 1 uses plain CE via alpha=1.0 would still skip incorrect
        # tokens; instead use the dedicated CE loss:
        step_fn = make_train_step(cfg, ctx, gk=gk, opt_cfg=opt_cfg)
        if stage == 1:
            from repro.training.loop import make_train_step as mk
            def apply_fn(params, batch):
                return tfm.forward(params, cfg, batch["inputs"], ctx,
                                   batch.get("patches"), return_aux=True)
            step_fn = mk(apply_fn, opt_cfg, loss_kind="ce", aux_weight=0.01)
        opt_state = optim.adamw_init(params)
        t0 = time.time()
        it_forever = it.forever()
        for i in range(steps):
            b = next(it_forever)
            batch = {"tokens": jnp.asarray(b["tokens"]),
                     "targets": jnp.asarray(b["targets"])}
            if "loss_mask" in b:
                batch["loss_mask"] = jnp.asarray(b["loss_mask"])
            if stage == 1:
                batch["inputs"] = batch["tokens"]
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if (i + 1) % args.log_every == 0 or i == 0:
                m = {k: round(float(v), 4) for k, v in metrics.items()
                     if jnp.ndim(v) == 0}
                print(f"stage{stage} step {i+1}/{steps} "
                      f"({(time.time()-t0)/(i+1):.2f}s/step): {m}")
        if args.ckpt:
            path = f"{args.ckpt}/stage{stage}"
            checkpoint.save_checkpoint(path, params, step=steps)
            print(f"checkpoint -> {path}")
    return params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--preset", default=None, choices=[None, "100m"])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--task", default="stream", choices=["stream", "qa"])
    ap.add_argument("--n-train", type=int, default=2048)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--stage1-steps", type=int, default=100)
    ap.add_argument("--stage2-steps", type=int, default=50)
    ap.add_argument("--alpha", type=float, default=0.3)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
