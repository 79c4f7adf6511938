"""Production training launcher: mesh + sharded state + fault-tolerant loop.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --mesh 1x1 --smoke --steps 20          # single device, CPU
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --mesh 2x4 --smoke --steps 10          # 8 host devices, dp=2 tp=4

On a real pod the same entrypoint takes --mesh 16x16 / 2x16x16 (the
dry-run-validated configurations) — jax.distributed.initialize() is called
when JAX_COORDINATOR_ADDRESS is set, so multi-host launch is `srun/gxm`
of this module on every host.
"""
from __future__ import annotations

import argparse
import logging
import os

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import get_config
from ..data.pipeline import DataConfig, SyntheticPipeline
from ..models import lm
from ..models.perf import TUNED, set_perf
from ..sharding.env import use_mesh
from .mesh import make_mesh
from ..train.optimizer import AdamWConfig, OptState, init_opt_state
from ..train.train_step import train_step
from ..ckpt.checkpoint import CheckpointManager


def parse_mesh(s: str):
    dims = tuple(int(x) for x in s.split("x"))
    axes = {1: ("data",), 2: ("data", "model"),
            3: ("pod", "data", "model")}[len(dims)]
    return make_mesh(dims, axes)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro-launch-train")
    ap.add_argument("--perf", action="store_true")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO)
    if os.environ.get("JAX_COORDINATOR_ADDRESS"):
        jax.distributed.initialize()
    if args.perf:
        set_perf(TUNED)

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = parse_mesh(args.mesh)
    with use_mesh(mesh) as env:
        from .dryrun import _resolve_tree
        params, specs = lm.init_params(cfg, jax.random.key(0))
        p_shard = _resolve_tree(env, specs)
        params = jax.tree.map(jax.device_put, params, p_shard)
        opt = init_opt_state(params)
        o_shard = OptState(NamedSharding(mesh, P()), p_shard, p_shard)
        ocfg = AdamWConfig(warmup_steps=5, total_steps=args.steps)
        step_fn = jax.jit(lambda p, o, b: train_step(cfg, ocfg, p, o, b),
                          in_shardings=(p_shard, o_shard, None),
                          out_shardings=(p_shard, o_shard, None),
                          donate_argnums=(0, 1))
        pipe = SyntheticPipeline(cfg, DataConfig(args.batch, args.seq))
        ckpt = CheckpointManager(args.ckpt_dir)
        start = ckpt.latest_step() or 0
        if start:
            state = ckpt.restore({"params": params, "opt": opt})
            params, opt = state["params"], state["opt"]
            print(f"resumed from step {start}")
        for step in range(start, args.steps):
            params, opt, m = step_fn(params, opt, pipe.batch_at(step))
            if (step + 1) % 5 == 0:
                print(f"step {step+1}: loss={float(m['loss']):.4f} "
                      f"gnorm={float(m['grad_norm']):.3f}")
        ckpt.save(args.steps, {"params": params, "opt": opt}, blocking=True)
        print(f"done; checkpoint at {args.ckpt_dir}")


if __name__ == "__main__":
    main()
