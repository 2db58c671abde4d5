"""Optional pipeline parallelism: 1F1B-style microbatch rotation via
shard_map + collective_permute over a dedicated 'stage' mesh axis.

The 40-cell dry-run matrix uses DP/FSDP/TP/SP/EP (DESIGN.md section 5); PP is
provided as a composable feature for depth-dominated models on meshes where
a stage axis is carved out of the data axis (e.g. (stage=4, data=4,
model=16)).  The implementation here is the GPipe-schedule special case
expressed with jax.lax collectives:

  * the layer stack is split into S stages; stage s holds its own params;
  * a shard_map over the 'stage' axis runs, per rotation step, the local
    stage on the activation block it currently holds, then
    collective_permute's activations to the next stage;
  * M >= S microbatches flow through; total steps = S + M - 1 (bubble
    fraction (S-1)/(S+M-1), reported by `bubble_fraction`).

Lowering this under the production mesh is exercised by
tests/test_distributed.py (4-stage mesh over forced host devices) and the
dryrun --set pipeline_stages=N path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_stages + n_microbatches - 1)


def pipeline_apply(mesh: Mesh, stage_fn, stage_params, x_microbatches,
                   axis: str = "stage"):
    """Run a GPipe rotation.

    stage_fn(params, x) -> x  : one stage's forward on one microbatch.
    stage_params          : pytree whose leaves have a leading stage dim
                            (sharded over `axis`).
    x_microbatches        : (M, mb, ...) microbatched activations, all
                            resident on stage 0's shard initially.
    Returns (M, mb, ...) outputs as produced by the LAST stage.
    """
    n_stages = mesh.shape[axis]
    m = x_microbatches.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_stage(params, xs):
        # params: this stage's slice (leading dim 1) ; xs: (M, mb, ...)
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        stage_idx = jax.lax.axis_index(axis)
        xs = xs[0]  # (M, mb, ...) local copy
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)
        total = n_stages + m - 1

        def body(t, carry):
            buf, outs = carry
            # stage 0 injects microbatch t (if any); others use what arrived
            use_inject = jnp.logical_and(stage_idx == 0, t < m)
            inject = jax.lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, m - 1), axis=0, keepdims=False)
            cur = jnp.where(use_inject, inject, buf)
            y = stage_fn(params, cur)
            # last stage records its output for microbatch (t - S + 1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            record = jnp.logical_and(stage_idx == n_stages - 1,
                                     t >= n_stages - 1)
            outs = jax.lax.cond(
                record,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, out_idx, axis=0),
                lambda o: o, outs)
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, total, body, (buf, outs))
        return outs[None]  # restore stage-leading dim

    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    # replicate microbatches across stages (each stage only *uses* its turn)
    xs_tiled = jnp.broadcast_to(x_microbatches[None],
                                (n_stages,) + x_microbatches.shape)
    outs = fn(stage_params, xs_tiled)
    return outs[-1]  # last stage's recorded outputs
