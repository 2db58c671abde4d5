"""Sharding rules: logical activation constraints + path-based param specs.

Mesh convention (fixed by the production spec):
    single-pod:  (data=16, model=16)
    multi-pod:   (pod=2, data=16, model=16)

`DP_AXES` is ('pod', 'data') when the pod axis exists, else ('data',).

Parameter rules are path-based (MaxText-style): tree paths are matched by
the LAST matching rule key (substring match), so arch files never annotate
weights — the rules below encode TP (model axis on head/ffn dims), ZeRO-3 /
FSDP (data axis on the complementary dim) and EP (experts on model axis).
Stacked scan params get the leading layer axis unsharded automatically.
"""

from __future__ import annotations

import re

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------


def current_mesh() -> Mesh | None:
    """The ambient mesh set by `jax.sharding.set_mesh`, or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def mesh_axis_names() -> tuple[str, ...]:
    m = current_mesh()
    return tuple(m.axis_names) if m is not None else ()


def mesh_devices(mesh: Mesh) -> list:
    """The mesh's devices as a flat list in mesh order — the per-shard
    placement the index partition layer keys on (shard s lives on
    ``mesh_devices(mesh)[s]``).  Abstract meshes (get_abstract_mesh) carry
    no concrete devices; fall back to the process device list, which is
    what an abstract mesh of the whole host means."""
    devs = getattr(mesh, "devices", None)
    if devs is not None:
        return [d for d in np.asarray(devs).flat]
    return list(jax.devices())[: mesh.size]


def dp_axes() -> tuple[str, ...]:
    names = mesh_axis_names()
    return tuple(a for a in ("pod", "data") if a in names)


def constrain(x: jnp.ndarray, *axes) -> jnp.ndarray:
    """with_sharding_constraint if a mesh is active; no-op otherwise.

    axes entries: None, an axis name, a tuple of names, or 'dp' which expands
    to the data-parallel axes present in the current mesh.
    """
    if current_mesh() is None:
        return x
    names = mesh_axis_names()

    def resolve(a):
        if a == "dp":
            got = dp_axes()
            return got if got else None
        if isinstance(a, tuple):
            kept = tuple(n for n in a if n in names)
            return kept if kept else None
        if a is not None and a not in names:
            return None
        return a

    spec = P(*[resolve(a) for a in axes])
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


# ---------------------------------------------------------------------------
# parameter partition rules (path substring -> PartitionSpec axes for the
# trailing dims; leading stacked/scan dims are padded with None)
# ---------------------------------------------------------------------------

# Order matters: later rules override earlier ones.
_PARAM_RULES: list[tuple[str, tuple]] = [
    # default: replicate
    (r".*", ()),
    # embeddings: vocab on model (TP), d_model on data (FSDP)
    (r"embed/table", ("model", "data")),
    (r"lm_head", ("data", "model")),  # (D, V)
    (r"hashed_embed/table", ("model", "data")),
    # attention
    (r"attn/wq$", ("data", "model")),
    (r"attn/wk$", ("data", "model")),
    (r"attn/wv$", ("data", "model")),
    (r"attn/wo$", ("model", "data")),
    (r"attn/b[qkv]$", ("model",)),
    # MLA: lora ranks replicated-ish; big projections TP on head dim
    (r"attn/wq_a$", ("data", None)),
    (r"attn/wq_b$", (None, "model")),
    (r"attn/wkv_a$", ("data", None)),
    (r"attn/wkv_b$", (None, "model")),
    # dense mlp
    (r"mlp/w_gate$", ("data", "model")),
    (r"mlp/w_up$", ("data", "model")),
    (r"mlp/w_down$", ("model", "data")),
    # moe: experts on model (EP), FSDP on d_model dim
    (r"moe/router$", ("data", None)),
    (r"moe/w_gate$", ("model", "data", None)),
    (r"moe/w_up$", ("model", "data", None)),
    (r"moe/w_down$", ("model", None, "data")),
    (r"moe/shared/w_gate$", ("data", "model")),
    (r"moe/shared/w_up$", ("data", "model")),
    (r"moe/shared/w_down$", ("model", "data")),
    # mamba
    (r"mamba/in_proj$", ("data", "model")),
    (r"mamba/conv_w$", ("model", None)),
    (r"mamba/conv_b$", ("model",)),
    (r"mamba/x_proj$", ("model", None)),
    (r"mamba/dt_w$", (None, "model")),
    (r"mamba/dt_b$", ("model",)),
    (r"mamba/a_log$", ("model", None)),
    (r"mamba/d$", ("model",)),
    (r"mamba/out_proj$", ("model", "data")),
    # xlstm
    (r"lstm/w[qkvz]$", ("data", "model")),
    (r"lstm/w_up$", ("data", "model")),
    (r"lstm/w_z$", ("data", "model")),
    (r"lstm/w_down$", ("model", "data")),
    (r"lstm/w_[ifo]$", ("data", None)),
    (r"lstm/r_[zifo]$", ("model", None)),
    (r"lstm/wo$", ("model", "data")),
    (r"lstm/out_proj$", ("model", "data")),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


# When enabled (EXPERIMENTS.md section Perf, deepseek-v3 iteration 2), MoE
# expert weights are sharded over BOTH mesh axes on the expert dim — each
# chip holds whole experts (256 = 16x16), trading the per-layer ZeRO weight
# regather for the (smaller) token all-to-all.  Toggled per-run by dryrun
# --set moe_2d=true; falls back automatically when E doesn't divide.
_MOE_2D = False


def set_moe_2d(enabled: bool) -> None:
    global _MOE_2D
    _MOE_2D = bool(enabled)


def spec_for_path(path, leaf) -> P:
    s = _path_str(path)
    axes: tuple = ()
    for pattern, rule in _PARAM_RULES:
        if re.search(pattern, s):
            axes = rule
    if _MOE_2D and re.search(r"moe/w_(gate|up|down)$", s):
        # whole experts resident per chip: expert dim over (model, data)
        axes = (("model", "data"), None, None)
    ndim = getattr(leaf, "ndim", len(getattr(leaf, "shape", ())))
    if len(axes) > ndim:
        axes = axes[-ndim:] if ndim else ()
    pad = ndim - len(axes)
    full = (None,) * pad + tuple(axes)
    # drop axes that would not divide the dim evenly — GSPMD requires
    # divisibility for named sharding on weights we feed as in_shardings.
    mesh = current_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes)) if mesh is not None else {}

    def axis_size(ax) -> int:
        if isinstance(ax, tuple):
            n = 1
            for a in ax:
                n *= sizes.get(a, 1)
            return n
        return sizes.get(ax, 1)

    cleaned = []
    for dim, ax in zip(getattr(leaf, "shape", (None,) * ndim), full):
        if ax is None:
            cleaned.append(None)
            continue
        size = axis_size(ax)
        if dim is None or size <= 1 or dim % size:
            cleaned.append(None)
        else:
            cleaned.append(ax)
    return P(*cleaned)


def param_specs(params) -> dict:
    """PartitionSpec tree mirroring a param tree."""
    return jax.tree_util.tree_map_with_path(spec_for_path, params)


def param_shardings(mesh: Mesh, params):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, spec_for_path(path, leaf)), params
    )


def batch_spec(ndim: int) -> P:
    """Batch-leading activation spec: (dp, None, ...)."""
    got = dp_axes()
    lead = got if got else None
    return P(lead, *([None] * (ndim - 1)))


def batch_sharding_for(mesh, shape: tuple[int, ...]):
    """NamedSharding for a batch-leading array, dropping the dp axes when the
    batch dim doesn't divide them (e.g. long_500k's global_batch=1)."""
    from jax.sharding import NamedSharding

    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    total = 1
    for a in axes:
        total *= sizes[a]
    lead = axes if (axes and shape and shape[0] % total == 0) else None
    return NamedSharding(mesh, P(lead, *([None] * (len(shape) - 1))))


# ---------------------------------------------------------------------------
# decode-cache sharding rules
#
# Cache entries are stacked (n_repeat, batch, ...).  Strategy per entry:
#   * GQA K/V (R, B, H, S, dh): heads on 'model' when H divides it, else
#     SEQUENCE-sharded cache (flash-decode style partial softmax combine);
#     batch on dp when divisible.
#   * MLA latent (R, B, S, r): sequence on 'model' (no head dim by design).
#   * SSM / LSTM states: feature dims on 'model' where divisible.
# The divisibility cleanup below auto-drops axes that don't divide (e.g.
# batch=1 for long_500k replicates instead of failing).
# ---------------------------------------------------------------------------

_CACHE_RULES: list[tuple[str, tuple]] = [
    (r".*", ()),
    (r"mixer/[kv]$", ("dp", "model", None, None)),        # (B,H,S,dh) heads
    (r"mixer/[kv]_scale$", ("dp", "model", None, None)),
    (r"mixer/c_kv$", ("dp", "model", None)),               # (B,S,r) seq
    (r"mixer/c_scale$", ("dp", "model", None)),
    (r"mixer/k_rope$", ("dp", "model", None)),
    (r"mixer/conv$", ("dp", None, "model")),               # (B,K-1,ED)
    (r"mixer/ssm$", ("dp", "model", None)),                # (B,ED,N)
    (r"mixer/c$", ("dp", None, "model", None)),            # mlstm (B,H,dh,dh)
    (r"mixer/n$", ("dp", None, "model")),
    (r"mixer/m$", ("dp", None)),
    (r"mixer/h$", ("dp", "model")),                        # slstm (B,d)
]


def cache_spec_for_path(path, leaf, kv_heads: int | None = None) -> P:
    s = _path_str(path)
    axes: tuple = ()
    for pattern, rule in _CACHE_RULES:
        if re.search(pattern, s):
            axes = rule
    mesh = current_mesh()
    names = mesh_axis_names()
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes)) if mesh is not None else {}
    model_size = sizes.get("model", 1)
    # GQA fallback: if the head dim doesn't divide 'model', shard SEQ instead.
    if re.search(r"mixer/[kv](_scale)?$", s) and kv_heads is not None:
        if model_size > 1 and kv_heads % model_size:
            axes = ("dp", None, "model", None)
    ndim = leaf.ndim
    resolved = []
    for a in axes:
        if a == "dp":
            got = dp_axes()
            resolved.append(got if got else None)
        elif a is not None and a not in names:
            resolved.append(None)
        else:
            resolved.append(a)
    pad = ndim - len(resolved)
    full = [None] * pad + resolved
    cleaned = []
    for dim, ax in zip(leaf.shape, full):
        if ax is None:
            cleaned.append(None)
            continue
        if isinstance(ax, tuple):
            size = 1
            for a in ax:
                size *= sizes.get(a, 1)
        else:
            size = sizes.get(ax, 1)
        if size <= 1 or dim % size:
            cleaned.append(None)
        else:
            cleaned.append(ax)
    return P(*cleaned)


def cache_specs(caches, kv_heads: int | None = None):
    return jax.tree_util.tree_map_with_path(
        lambda p, l: cache_spec_for_path(p, l, kv_heads), caches)
