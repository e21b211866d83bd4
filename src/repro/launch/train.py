"""Training driver — runnable end-to-end on CPU at reduced scale, and the
same code path the dry-run lowers at production scale.

    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b \
        --reduced --steps 50 --batch 8 --seq 64

Features: deterministic data pipeline, AdamW, microbatch accumulation,
periodic checkpointing + restart-from-latest (fault tolerance), optional
int8 error-feedback gradient compression, optional approximation policy
(the paper's technique applied to the LM).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import ckpt
from ..configs import get_config
from ..data.pipeline import TokenPipeline
from ..models import ApproxPolicy, reduced
from ..models.common import init_tree, with_dtype
from ..models.transformer import param_specs
from ..optim.adamw import AdamW
from ..train.step import init_state, make_train_step

__all__ = ["train_loop", "main"]


def train_loop(
    cfg,
    *,
    steps: int = 50,
    batch: int = 8,
    seq: int = 64,
    n_micro: int = 1,
    lr: float = 1e-3,
    ckpt_dir: str | None = None,
    ckpt_every: int = 25,
    compress: bool = False,
    policy: ApproxPolicy | None = None,
    seed: int = 0,
    log_every: int = 10,
    attn_chunk: int = 64,
    scan_chunk: int = 16,
):
    pipe = TokenPipeline(cfg.vocab_size, batch, seq, seed=seed)
    opt = AdamW(lr=lr, warmup_steps=max(steps // 10, 1),
                moment_dtype=cfg.moment_dtype)
    step_fn = jax.jit(make_train_step(
        cfg, opt, n_micro=n_micro, policy=policy, compress=compress,
        attn_chunk=attn_chunk, scan_chunk=scan_chunk,
    ), donate_argnums=(0,))

    # master weights in the config's training type (the declaration holds
    # the serving types)
    specs = with_dtype(param_specs(cfg), cfg.param_dtype)
    start = 0
    state = None
    if ckpt_dir is not None:
        latest = ckpt.latest_step(ckpt_dir)
        if latest is not None:
            like = init_state(
                init_tree(specs, jax.random.PRNGKey(seed)), opt,
                compress=compress,
            )
            state = ckpt.restore(ckpt_dir, latest, like)
            start = latest
            print(f"[train] restored checkpoint @ step {latest}")
    if state is None:
        params = init_tree(specs, jax.random.PRNGKey(seed))
        state = init_state(params, opt, compress=compress)

    losses = []
    t0 = time.perf_counter()
    for step in range(start, steps):
        b = pipe.batch_at(step)
        batch_dev = {
            "tokens": jnp.asarray(b["tokens"]),
            "labels": jnp.asarray(b["labels"]),
        }
        if cfg.is_encoder_decoder:
            batch_dev["enc_embeds"] = jax.random.normal(
                jax.random.PRNGKey(step), (batch, seq, cfg.d_model),
                jnp.float32) * 0.1
        if cfg.frontend == "vision":
            batch_dev["embeds"] = jax.random.normal(
                jax.random.PRNGKey(step),
                (batch, cfg.frontend_len, cfg.d_model), jnp.float32) * 0.1
        state, metrics = step_fn(state, batch_dev)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % log_every == 0 or step == steps - 1:
            dt = time.perf_counter() - t0
            print(f"[train] step {step:5d} loss={loss:8.4f} "
                  f"ce={float(metrics['ce']):8.4f} "
                  f"gnorm={float(metrics['grad_norm']):7.3f} ({dt:5.1f}s)",
                  flush=True)
        if ckpt_dir is not None and (step + 1) % ckpt_every == 0:
            ckpt.save(ckpt_dir, step + 1, state)
    return state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--approx", default=None,
                    help="apply a circuit to ffn projections, e.g. mul8s_trunc2")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    policy = None
    if args.approx:
        policy = ApproxPolicy({
            "ffn_in": (args.approx, None), "ffn_out": (args.approx, None),
        })
    _, losses = train_loop(
        cfg, steps=args.steps, batch=args.batch, seq=args.seq,
        n_micro=args.n_micro, lr=args.lr, ckpt_dir=args.ckpt_dir,
        compress=args.compress, policy=policy,
    )
    print(f"[train] first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
