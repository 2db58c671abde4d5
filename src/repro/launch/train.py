"""Production training launcher: mesh + sharded state + trainer loop.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --mesh 1x1 --steps 20 --batch 8 --seq 256 --reduced

On real hardware the mesh comes from make_production_mesh(); on this
container any mesh shape that matches jax.device_count() works (1x1 by
default).  The launcher wires: config -> sharded init -> (optional EF-sign
cross-pod grad compression) -> jit(train_step, in_shardings=...) ->
Trainer loop with checkpoints/heartbeats.
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import tempfile

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ParallelConfig, TrainConfig,
                                reduced_for_smoke)
from repro.configs.registry import get_config
from repro.data.pipeline import BatchPipeline, PipelineConfig
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.train import optimizer as opt
from repro.train.train_step import make_train_step

_log = logging.getLogger("repro.launch.train")


def main() -> None:
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 16x16")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU demo)")
    ap.add_argument("--dedup", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_for_smoke(cfg)
    data_p, model_p = (int(v) for v in args.mesh.split("x"))
    mesh = make_mesh((data_p, model_p), ("data", "model"))
    pcfg = ParallelConfig(remat="block", sequence_parallel=model_p > 1,
                          zero3=data_p > 1)
    tcfg = TrainConfig(total_steps=args.steps)

    with jax.sharding.set_mesh(mesh):
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        psh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), shd.param_specs(params),
            is_leaf=lambda x: isinstance(x, P))
        params = jax.tree_util.tree_map(jax.device_put, params, psh)
        opt_state = opt.init_state(params, cfg.precision.moment_dtype)
        osh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), shd.param_specs(opt_state),
            is_leaf=lambda x: isinstance(x, P))
        opt_state = jax.tree_util.tree_map(jax.device_put, opt_state, osh)

        step = jax.jit(make_train_step(cfg, pcfg, tcfg),
                       donate_argnums=(0, 1))
        pipe = BatchPipeline(PipelineConfig(
            vocab_size=cfg.vocab_size, seq_len=args.seq,
            global_batch=args.batch, dedup=args.dedup))
        _log.info("mesh=%s params=%.1fM arch=%s", mesh.shape,
                  T.count_params(params) / 1e6, cfg.name)
        for i in range(args.steps):
            batch = {k: jax.numpy.asarray(v) for k, v in next(pipe).items()}
            params, opt_state, metrics = step(params, opt_state, batch)
            if (i + 1) % 5 == 0 or i == 0:
                _log.info("step %4d loss=%.4f gnorm=%.3f", i + 1,
                          float(metrics["loss"]),
                          float(metrics["grad_norm"]))
        pipe.close()
        _log.info("done")


if __name__ == "__main__":
    main()
