"""Multi-host cluster bootstrap — the piece that turns the single-process
drivers into a real pod launch.

On a TPU pod each host runs the same program; JAX's distributed runtime
assembles the global device mesh. This module:

  * initializes jax.distributed from standard env vars
    (COORDINATOR_ADDRESS, NUM_PROCESSES, PROCESS_ID — or TPU metadata
    auto-detection when none are set),
  * computes each host's shard of the global batch (the data pipeline is
    counter-based, so hosts need no coordination — straggler/elastic
    story, DESIGN.md §5),
  * wraps train_loop/serve_batch with host-local data feeding via
    jax.make_array_from_process_local_data.

    # per host (example: 2 pods x 64 hosts x 4 chips):
    COORDINATOR_ADDRESS=host0:1234 NUM_PROCESSES=128 PROCESS_ID=$i \
      python -m repro.launch.cluster --arch granite-8b --steps 1000

scripts/launch_pod.sh shows the full invocation.
"""

from __future__ import annotations

import argparse
import os
from typing import Optional

__all__ = ["init_distributed", "host_rows", "main"]


def init_distributed() -> tuple:
    """Initialize jax.distributed from the environment; returns
    (process_index, process_count).  No-op fallback for single-process
    (CPU container) runs so the module stays testable offline."""
    import jax

    coord = os.environ.get("COORDINATOR_ADDRESS")
    nproc = os.environ.get("NUM_PROCESSES")
    pid = os.environ.get("PROCESS_ID")
    if coord and nproc:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(nproc),
            process_id=int(pid or 0),
        )
    elif os.environ.get("TPU_WORKER_HOSTNAMES"):
        jax.distributed.initialize()  # TPU metadata auto-detection
    return jax.process_index(), jax.process_count()


def host_rows(global_batch: int, process_index: int, process_count: int) -> range:
    """The contiguous row range of the global batch this host produces.
    Contiguity matches the mesh's device order so host data lands on the
    host's own devices (no cross-host scatter)."""
    per = global_batch // process_count
    return range(process_index * per, (process_index + 1) * per)


def make_global_batch(pipe, step: int, mesh, rules=None):
    """Assemble the globally-sharded batch from host-local rows."""
    import jax
    import jax.numpy as jnp

    from ..dist.sharding import sharding_for

    pi, pc = jax.process_index(), jax.process_count()
    local = pipe.batch_at(step, rows=host_rows(pipe.batch, pi, pc))
    out = {}
    for k, v in local.items():
        gshape = (pipe.batch,) + v.shape[1:]
        sh = sharding_for(("batch",) + (None,) * (v.ndim - 1), gshape,
                          mesh, rules)
        if pc == 1:
            out[k] = jax.device_put(jnp.asarray(v), sh)
        else:
            out[k] = jax.make_array_from_process_local_data(sh, v, gshape)
    return out


def main(argv: Optional[list] = None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (single-host validation)")
    args = ap.parse_args(argv)

    pi, pc = init_distributed()
    import jax

    print(f"[cluster] process {pi}/{pc}, local devices: "
          f"{jax.local_device_count()}, global: {jax.device_count()}")

    from ..configs import get_config
    from ..data.pipeline import TokenPipeline
    from ..dist.sharding import rule_overrides
    from ..models import reduced as reduce_cfg
    from ..models.common import init_tree, with_dtype
    from ..models.transformer import param_specs
    from ..optim.adamw import AdamW
    from ..train.step import init_state, make_train_step
    from ..checkpoint import ckpt
    from .mesh import make_production_mesh
    from .shapes import cell_rules, n_microbatches

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)

    if jax.device_count() >= 512:
        mesh = make_production_mesh(multi_pod=True)
    elif jax.device_count() >= 256:
        mesh = make_production_mesh()
    else:  # validation mesh on whatever is available
        from ..dist.compat import make_mesh

        mesh = make_mesh((jax.device_count(),), ("data",))

    rules = cell_rules(cfg, "train_4k", mesh)
    pipe = TokenPipeline(cfg.vocab_size, args.batch, args.seq, seed=0)
    opt = AdamW(moment_dtype=cfg.moment_dtype)
    nm = n_microbatches(cfg, mesh) if not args.reduced else 1
    step_fn = jax.jit(
        make_train_step(cfg, opt, n_micro=nm), donate_argnums=(0,)
    )

    from ..dist.compat import mesh_context

    with mesh_context(mesh), rule_overrides(rules):
        specs = with_dtype(param_specs(cfg), cfg.param_dtype)
        latest = ckpt.latest_step(args.ckpt_dir) if pi == 0 else None
        params = init_tree(specs, jax.random.PRNGKey(0))
        state = init_state(params, opt)
        start = 0
        if latest is not None:
            state = ckpt.restore(args.ckpt_dir, latest, state)
            start = latest
            print(f"[cluster] restored step {latest}")
        for step in range(start, args.steps):
            batch = make_global_batch(pipe, step, mesh, rules)
            state, metrics = step_fn(state, batch)
            if step % 10 == 0 and pi == 0:
                print(f"[cluster] step {step} loss={float(metrics['loss']):.4f}",
                      flush=True)
            if (step + 1) % 100 == 0 and pi == 0:
                ckpt.save(args.ckpt_dir, step + 1, state)


if __name__ == "__main__":
    main()
