"""Serving driver: batched prefill + autoregressive decode, CPU-runnable
at reduced scale.

    PYTHONPATH=src python -m repro.launch.serve --arch falcon-mamba-7b \
        --reduced --batch 4 --prompt-len 32 --gen 16

The approximate-serving path draws its policy from a stored Pareto
front instead of a hand-picked circuit: ``--front front.json --tier
budget`` loads the front (the ``GET /front`` payload shape, or a
``FrontCatalog.to_json`` file), resolves the tier to a genome, and
decodes it to the ``ApproxPolicy`` baked into the jitted steps.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from .. import compile_cache
from ..configs import get_config
from ..models import ApproxPolicy, reduced
from ..models.common import init_tree
from ..models.transformer import param_specs
from ..train.serve import Generator

__all__ = ["serve_batch", "policy_from_front", "main"]


def serve_batch(
    cfg,
    *,
    batch: int = 4,
    prompt_len: int = 32,
    gen: int = 16,
    policy: ApproxPolicy | None = None,
    seed: int = 0,
    params=None,
    prompts=None,
    generator: Generator | None = None,
):
    """Greedy-decode `gen` tokens for a batch of (synthetic by default)
    prompts.  Returns (tokens (b, prompt+gen), tokens/s).  A caller that
    has already compiled (or inspected) a ``Generator``'s steps passes it
    as ``generator``; it must have been built for ``cfg`` and ``policy``."""
    key = jax.random.PRNGKey(seed)
    if params is None:
        params = init_tree(param_specs(cfg), key)
    if prompts is None:
        prompts = jax.random.randint(
            key, (batch, prompt_len), 0, cfg.vocab_size)
    prompts = jnp.asarray(prompts, jnp.int32)
    if generator is not None and (generator.cfg != cfg
                                  or generator.policy is not policy):
        raise ValueError("generator was built for another cfg or policy")
    g = generator or Generator(cfg, policy=policy, attn_chunk=32,
                               scan_chunk=8)
    return g.generate(params, prompts, gen, key=key)


def policy_from_front(cfg, front_path: str, tier: str = "balanced"):
    """(policy, selection) for ``tier`` of the stored front at
    ``front_path`` — the CLI's bridge from a DSE campaign's output to a
    runnable serving configuration."""
    from ..accel.lm import LMAccelerator
    from ..serving import FrontCatalog

    cat = FrontCatalog.from_file(front_path)
    expect = f"lm:{cfg.name}"
    if cat.accel != expect:
        print(f"[serve] WARNING: front is for {cat.accel!r}, "
              f"serving {expect!r}")
    sel = cat.select(tier=tier)
    accel = LMAccelerator(cfg, use_reduced=False)
    policy = accel.policy_for_genome(
        sel.point.genome_array(), rank_genes=cat.rank_genes)
    return policy, sel


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="falcon-mamba-7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--approx", default=None,
                    help="hand-picked circuit for ffn_in/ffn_out")
    ap.add_argument("--front", default=None,
                    help="stored front JSON (GET /front shape); the "
                         "policy comes from its --tier operating point")
    ap.add_argument("--tier", default="balanced",
                    choices=("exact", "balanced", "budget"))
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    policy = None
    if args.front and args.approx:
        ap.error("--front and --approx are mutually exclusive")
    if args.front:
        policy, sel = policy_from_front(cfg, args.front, args.tier)
        labels = " ".join(
            f"{k}={v:.3g}" for k, v in sel.point.labels.items())
        print(f"[serve] tier={args.tier} genome={list(sel.point.genome)} "
              f"({labels})")
    elif args.approx:
        policy = ApproxPolicy({
            "ffn_in": (args.approx, None), "ffn_out": (args.approx, None),
        })
    tokens, tps = serve_batch(
        cfg, batch=args.batch, prompt_len=args.prompt_len, gen=args.gen,
        policy=policy,
    )
    print(f"[serve] {cfg.name}: generated {tokens.shape} @ {tps:.1f} tok/s")
    print(tokens[0])


if __name__ == "__main__":
    main()
