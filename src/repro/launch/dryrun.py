import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell.

MUST be executed as its own process (python -m repro.launch.dryrun ...): the
two lines above run before any jax import so the 512 placeholder host
devices exist when jax initialises.  Smoke tests and benches never import
this module, so they keep seeing 1 device.

Per cell this driver:
  1. builds abstract params / optimizer state / batch or caches (ShapeDtype
     structs only — no allocation),
  2. jits the mode's step function with NamedShardings from the rules in
     repro.distributed.sharding,
  3. lowers + compiles under the production mesh,
  4. records memory_analysis(), cost_analysis() and the collective schedule
     parsed from the partitioned HLO into experiments/dryrun/<cell>.json
     for the roofline report (launch/roofline.py, benchmarks/).
"""

import argparse
import dataclasses
import json
import logging
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ParallelConfig, Precision, SHAPES,
                                TrainConfig)
from repro.configs.registry import ARCH_IDS, get_config
from repro.distributed import sharding as shd
from repro.launch import roofline as rl
from repro.launch import specs as sp
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.serve.engine import make_serve_step
from repro.train import optimizer as opt
from repro.train.train_step import make_train_step

_log = logging.getLogger("repro.launch.dryrun")

# ---------------------------------------------------------------------------
# per-cell presets (baseline parallel/memory knobs; hillclimbing edits these
# via --set overrides and records deltas in EXPERIMENTS.md section Perf)
# ---------------------------------------------------------------------------

DEFAULT_PCFG = dict(remat="block", sequence_parallel=True, zero3=True,
                    microbatches=1)

PRESETS: dict[tuple[str, str], dict] = {
    # 671B: bf16 moments (fit analysis in EXPERIMENTS.md), dispatch groups
    ("deepseek_v3_671b", "train_4k"): {"moment_dtype": "bfloat16"},
    ("dbrx_132b", "train_4k"): {"moment_dtype": "bfloat16"},
}


def _pcfg_for(arch: str, shape_name: str, overrides: dict) -> ParallelConfig:
    kw = dict(DEFAULT_PCFG)
    preset = PRESETS.get((arch, shape_name), {})
    kw.update({k: v for k, v in preset.items() if k in ParallelConfig.__dataclass_fields__})
    kw.update({k: v for k, v in overrides.items() if k in ParallelConfig.__dataclass_fields__})
    return ParallelConfig(**kw)


def _cfg_for(arch: str, shape_name: str, overrides: dict):
    cfg = get_config(arch)
    preset = PRESETS.get((arch, shape_name), {})
    merged = {**preset, **overrides}
    mdt = merged.get("moment_dtype")
    if mdt:
        cfg = dataclasses.replace(
            cfg, precision=dataclasses.replace(cfg.precision, moment_dtype=mdt))
    if cfg.moe is not None:
        moe_kw = {k: v for k, v in merged.items()
                  if k in ("capacity_factor", "dispatch_dtype", "group_size",
                           "top_k")}
        if moe_kw:
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, **moe_kw))
    return cfg


# ---------------------------------------------------------------------------
# lowering helpers
# ---------------------------------------------------------------------------


def _named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def lower_cell(cfg, shape, mesh, pcfg: ParallelConfig, moe_2d: bool = False):
    """Returns (lowered, aux_info)."""
    tcfg = TrainConfig()
    shd.set_moe_2d(moe_2d)
    with jax.sharding.set_mesh(mesh):
        params_abs = sp.abstract_params(cfg)
        pspecs = shd.param_specs(params_abs)
        psh = _named(mesh, pspecs)
        if shape.mode == "train":
            opt_abs = jax.eval_shape(
                lambda p: opt.init_state(p, cfg.precision.moment_dtype),
                params_abs)
            ospecs = shd.param_specs(opt_abs)
            osh = _named(mesh, ospecs)
            batch_abs = sp.batch_specs(cfg, shape)
            bsh = {k: shd.batch_sharding_for(mesh, v.shape)
                   for k, v in batch_abs.items()}
            step = make_train_step(cfg, pcfg, tcfg)
            jitted = jax.jit(step, in_shardings=(psh, osh, bsh),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_abs, opt_abs, batch_abs)
        elif shape.mode == "prefill":
            batch_abs = sp.batch_specs(cfg, shape)
            bsh = {k: shd.batch_sharding_for(mesh, v.shape)
                   for k, v in batch_abs.items()}

            def fwd(params, batch):
                logits, aux = T.forward(cfg, params, batch, pcfg)
                return logits

            jitted = jax.jit(fwd, in_shardings=(psh, bsh))
            lowered = jitted.lower(params_abs, batch_abs)
        else:  # decode
            dspecs = sp.decode_specs(cfg, shape, pcfg.kv_cache_dtype)
            csh = _named(mesh, shd.cache_specs(dspecs["caches"],
                                               kv_heads=cfg.n_kv_heads))
            tok_sh = shd.batch_sharding_for(mesh, dspecs["tokens"].shape)
            pos_sh = NamedSharding(mesh, P())
            serve = make_serve_step(cfg, pcfg)
            if cfg.kind == "encdec":
                enc_sh = shd.batch_sharding_for(mesh, dspecs["enc_out"].shape)
                jitted = jax.jit(
                    serve, in_shardings=(psh, csh, tok_sh, pos_sh, enc_sh),
                    donate_argnums=(1,))
                lowered = jitted.lower(params_abs, dspecs["caches"],
                                       dspecs["tokens"], dspecs["pos"],
                                       dspecs["enc_out"])
            else:
                jitted = jax.jit(serve,
                                 in_shardings=(psh, csh, tok_sh, pos_sh),
                                 donate_argnums=(1,))
                lowered = jitted.lower(params_abs, dspecs["caches"],
                                       dspecs["tokens"], dspecs["pos"])
    return lowered


def _depth_cfg(cfg, k: int):
    """Reduced-depth twin: first_k_dense + k repeats of the layer pattern
    (encoder reduced to k layers too — whisper scales both together)."""
    period = len(cfg.layer_pattern)
    kw = dict(n_layers=cfg.first_k_dense + k * period)
    if cfg.kind == "encdec":
        kw["n_encoder_layers"] = k
    return dataclasses.replace(cfg, **kw)


def _rest_repeats(cfg) -> int:
    return T.build_stages(cfg)[-1].n_repeat


def _cost_metrics(cfg, shape, mesh, pcfg, chips, moe_2d=False) -> dict:
    """flops/bytes/collectives via unrolled reduced-depth extrapolation.

    XLA's HloCostAnalysis counts while-loop bodies once (trip counts are
    ignored), so scanned stacks must be measured unrolled.  We lower k=1 and
    k=2 pattern repeats fully unrolled and extrapolate linearly to the full
    repeat count — exact because stage cost is linear in repeats."""
    samples = {}
    for k in (1, 2):
        cfg_k = _depth_cfg(cfg, k)
        pcfg_k = dataclasses.replace(pcfg, unroll_scan=True)
        lowered = lower_cell(cfg_k, shape, mesh, pcfg_k, moe_2d=moe_2d)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0] if cost else {}
        hlo = compiled.as_text()
        coll = rl.parse_collectives(hlo, default_group=chips)
        bytes_raw = float(cost.get("bytes accessed", 0.0))
        convert_b = rl.parse_convert_bytes(hlo)
        samples[k] = {
            "flops": float(cost.get("flops", 0.0)),
            # TPU-representative HBM bytes: CPU-backend f32<->bf16 convert
            # materialisation removed (see roofline.parse_convert_bytes)
            "bytes": max(bytes_raw - convert_b, 0.0),
            "bytes_raw": bytes_raw,
            "convert_bytes": convert_b,
            "coll_traffic": coll.traffic_bytes,
            "coll_raw": coll.raw_bytes,
            "coll_count": coll.count,
            "by_op": coll.by_op,
        }
    r_full = _rest_repeats(cfg)

    def extrap(key):
        m1, m2 = samples[1][key], samples[2][key]
        return m1 + max(m2 - m1, 0.0) * (r_full - 1)

    by_op = {}
    for op in set(samples[1]["by_op"]) | set(samples[2]["by_op"]):
        d1 = samples[1]["by_op"].get(op, {"count": 0, "bytes": 0.0, "traffic": 0.0})
        d2 = samples[2]["by_op"].get(op, {"count": 0, "bytes": 0.0, "traffic": 0.0})
        by_op[op] = {
            k2: d1[k2] + max(d2[k2] - d1[k2], 0) * (r_full - 1)
            for k2 in ("count", "bytes", "traffic")
        }
    return {
        "flops_per_device": extrap("flops"),
        "bytes_per_device": extrap("bytes"),
        "bytes_per_device_raw": extrap("bytes_raw"),
        "collective_traffic_bytes": extrap("coll_traffic"),
        "collective_raw_bytes": extrap("coll_raw"),
        "collective_count": int(extrap("coll_count")),
        "collectives_by_op": by_op,
        "cost_samples": {str(k): {kk: vv for kk, vv in v.items() if kk != "by_op"}
                         for k, v in samples.items()},
        "rest_repeats": r_full,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             overrides: dict, force: bool = False,
             tag: str = "") -> dict:
    mesh_name = "multipod" if multi_pod else "pod"
    cell_id = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    out_path = os.path.join(out_dir, cell_id + ".json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    shape = SHAPES[shape_name]
    cfg = _cfg_for(arch, shape_name, overrides)
    ok, reason = sp.shape_applicable(cfg, shape)
    record: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
        "mode": shape.mode, "overrides": overrides,
    }
    os.makedirs(out_dir, exist_ok=True)
    if not ok:
        record.update({"status": "skipped", "reason": reason})
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1)
        return record

    pcfg = _pcfg_for(arch, shape_name, overrides)
    moe_2d = bool(overrides.get("moe_2d", False))
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    try:
        # 1. full-config compile: proves the cell lowers/partitions, and
        #    provides the per-device memory analysis.
        t0 = time.perf_counter()
        lowered = lower_cell(cfg, shape, mesh, pcfg, moe_2d=moe_2d)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

        mem = compiled.memory_analysis()
        mem_rec = {}
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                mem_rec[attr] = int(v)

        # 2. cost pass: unrolled reduced-depth extrapolation (see helper).
        cost_rec = _cost_metrics(cfg, shape, mesh, pcfg, chips, moe_2d=moe_2d)

        n_active = rl.active_params(cfg)
        mf = rl.model_flops(cfg, shape, n_active)

        record.update({
            "status": "ok",
            "chips": chips,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory_analysis": mem_rec,
            "active_params": n_active,
            "model_flops": mf,
            "pcfg": {k: getattr(pcfg, k) for k in
                     ("microbatches", "remat", "sequence_parallel", "zero3",
                      "kv_cache_dtype")},
            **cost_rec,
        })
        roof = rl.analyze(record, chips)
        record["roofline"] = roof.as_dict()
        _log.info("[ok] %s: compile=%.1fs flops/dev=%.3g bytes/dev=%.3g "
                  "coll/dev=%.3gB dominant=%s", cell_id, t_compile,
                  record["flops_per_device"], record["bytes_per_device"],
                  record["collective_traffic_bytes"], roof.dominant)
    except Exception as e:  # record failures — they are bugs to fix
        record.update({"status": "error", "error": repr(e),
                       "traceback": traceback.format_exc()[-4000:]})
        _log.error("[ERR] %s: %r", cell_id, e)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def main() -> None:
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="shape name or 'all'")
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="variant tag for perf iterations")
    ap.add_argument("--set", action="append", default=[],
                    help="override knob, e.g. --set microbatches=8 "
                         "--set kv_cache_dtype=int8")
    args = ap.parse_args()

    overrides: dict = {}
    for item in args.set:
        k, v = item.split("=", 1)
        if v.isdigit():
            v = int(v)
        elif v in ("true", "false", "True", "False"):
            v = v in ("true", "True")
        else:
            try:
                v = float(v)
            except ValueError:
                pass
        overrides[k] = v

    archs = ARCH_IDS if args.arch == "all" else [args.arch.replace("-", "_")]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape_name in shapes:
            for multi_pod in meshes:
                rec = run_cell(arch, shape_name, multi_pod, args.out,
                               overrides, force=args.force, tag=args.tag)
                st = rec.get("status")
                n_ok += st == "ok"
                n_skip += st == "skipped"
                n_err += st == "error"
    _log.info("done: %d ok, %d skipped, %d errors", n_ok, n_skip, n_err)
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
