"""Sharding rules: parameters, optimizer state, caches, and batches.

Strategy (DESIGN.md §5):
  * batch            -> data axes ("pod", "data")
  * attention heads / ffn hidden / vocab / experts -> "model" (TP / EP)
  * parameter d_model dim -> data axes (FSDP / ZeRO) — used for BOTH training
    and inference so 480B-class models fit per-chip HBM
  * optimizer moments inherit the parameter sharding (elementwise)

Rules are resolved against concrete leaf shapes via ``eval_shape`` (no
allocation), with divisibility fallbacks: if a preferred axis does not divide
the dim, the next candidate (or replication) is used, so every assigned
architecture lowers on every mesh without bespoke tables.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = Any


def data_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def _fit(dim: int, mesh: Mesh, *candidates):
    """First candidate axis (or axis tuple) that divides ``dim``; None if no
    candidate fits."""
    for cand in candidates:
        if cand is None:
            continue
        if dim % axis_size(mesh, cand) == 0:
            return cand
    return None


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def param_spec(path: str, shape: tuple, mesh: Mesh, fsdp: bool = True) -> P:
    """PartitionSpec for one parameter leaf, keyed on its name + rank."""
    dp = data_axes(mesh) if fsdp else None
    mp = "model"
    name = path.split("/")[-1]
    nd = len(shape)

    def spec(*dims):
        # pad leading stacked-layer axes with None
        lead = nd - len(dims)
        return P(*([None] * lead + list(dims)))

    if name == "embed":                      # (V, d)
        # vocab REPLICATED for the lookup gather (a vocab-sharded table makes
        # SPMD fully rematerialize the gather); d over the data axes.  Tied
        # unembed uses get resharded by propagation.
        return P(None, _fit(shape[1], mesh, dp))
    if name in ("unembed",):                 # (d, V)
        return P(_fit(shape[0], mesh, dp), _fit(shape[1], mesh, mp))
    if name in ("pos_embed", "enc_pos_embed"):
        return P(None, _fit(shape[1], mesh, dp))
    if name in ("wq", "w_gate", "w_up", "in_proj") and nd >= 2:
        if name in ("w_gate", "w_up") and nd == 4:   # MoE (L, E, d, f)
            return P(None, _fit(shape[1], mesh, mp),
                     _fit(shape[2], mesh, dp), None)
        return spec(_fit(shape[-2], mesh, dp), _fit(shape[-1], mesh, mp))
    if name in ("wk", "wv"):
        return spec(_fit(shape[-2], mesh, dp), _fit(shape[-1], mesh, mp))
    if name in ("wo", "w_down", "out_proj"):
        if name == "w_down" and nd == 4:             # MoE (L, E, f, d)
            return P(None, _fit(shape[1], mesh, mp), None,
                     _fit(shape[3], mesh, dp))
        return spec(_fit(shape[-2], mesh, mp), _fit(shape[-1], mesh, dp))
    if name == "router":                     # (L, d, E)
        return spec(_fit(shape[-2], mesh, dp), None)
    if name == "conv_w":                     # (L, W, C)
        return spec(None, _fit(shape[-1], mesh, mp))
    # norms, biases, A_log, D, dt_bias, conv_b, norm_scale: replicated
    return P(*([None] * nd))


def cache_spec(path: str, shape: tuple, mesh: Mesh, batch_axis: int) -> P:
    """PartitionSpec for a serving-cache leaf.

    KV caches (.., B, S, KV, D): batch->data; kv-heads->model when divisible,
    else sequence->model (long-cache fallback, e.g. whisper's 20 heads).
    SSM states (.., B, H, Pdim, N): heads->model, else head-dim->model.
    """
    dp = data_axes(mesh)
    name = path.split("/")[-1]
    nd = len(shape)
    dims = [None] * nd
    if shape[batch_axis] % axis_size(mesh, dp) == 0:
        dims[batch_axis] = dp
    if name in ("k", "v", "dense_k", "dense_v", "cross_k", "cross_v"):
        kv_dim, s_dim = nd - 2, nd - 3
        if shape[kv_dim] % axis_size(mesh, "model") == 0:
            dims[kv_dim] = "model"
        elif shape[s_dim] % axis_size(mesh, "model") == 0:
            dims[s_dim] = "model"
    elif name == "ssm":                      # (.., B, H, P, N)
        h_dim, p_dim = nd - 3, nd - 2
        if shape[h_dim] % axis_size(mesh, "model") == 0:
            dims[h_dim] = "model"
        elif shape[p_dim] % axis_size(mesh, "model") == 0:
            dims[p_dim] = "model"
    elif name == "conv":                     # (.., B, W-1, C)
        if shape[-1] % axis_size(mesh, "model") == 0:
            dims[-1] = "model"
    return P(*dims)


def batch_spec(shape: tuple, mesh: Mesh) -> P:
    """Input batches: leading batch dim over the data axes when divisible."""
    dp = data_axes(mesh)
    dims = [None] * len(shape)
    if shape[0] % axis_size(mesh, dp) == 0:
        dims[0] = dp
    return P(*dims)


# ---------------------------------------------------------------------------
# Tree-level entry points
# ---------------------------------------------------------------------------

def param_shardings(mesh: Mesh, params_shape: Params, fsdp: bool = True) -> Params:
    """NamedShardings matching an eval_shape pytree of the parameters."""
    def rule(path, leaf):
        return NamedSharding(mesh, param_spec(_path_str(path), leaf.shape, mesh,
                                              fsdp=fsdp))
    return jax.tree_util.tree_map_with_path(rule, params_shape)


def opt_shardings(mesh: Mesh, param_sh: Params) -> Params:
    """Optimizer state shardings: moments inherit parameter shardings."""
    return {
        "m": param_sh,
        "v": param_sh,
        "step": NamedSharding(mesh, P()),
    }


def cache_shardings(mesh: Mesh, cache_shape: Params, batch_axes: dict) -> Params:
    def rule(path, leaf):
        key = _path_str(path).split("/")[-1]
        return NamedSharding(mesh, cache_spec(_path_str(path), leaf.shape, mesh,
                                              batch_axes[key]))
    return jax.tree_util.tree_map_with_path(rule, cache_shape)


def batch_shardings(mesh: Mesh, batch_shape: Params) -> Params:
    return jax.tree.map(
        lambda leaf: NamedSharding(mesh, batch_spec(leaf.shape, mesh)),
        batch_shape)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# In-model logical sharding constraints
# ---------------------------------------------------------------------------

_LOGICAL_AXES = {
    "batch": ("pod", "data"),
    "vocab": ("model",),
    "heads": ("model",),
    "experts": ("model",),
    "dmodel": ("data",),
    "seq": ("model",),
}


def _active_mesh():
    """The mesh set by ``jax.set_mesh``, or None (CPU single-device)."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _resolve(mesh, dim_size, name):
    """Mesh axes for a logical name if they exist and divide dim_size."""
    if name is None:
        return None
    axes = tuple(a for a in _LOGICAL_AXES[name] if a in mesh.axis_names)
    if not axes:
        return None
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return axes if dim_size % size == 0 else None


def logical_constraint(x, *logical):
    """with_sharding_constraint by LOGICAL axis names; a no-op when no mesh
    context is active or the named axes don't exist/divide (CPU tests run the
    same model code unconstrained)."""
    mesh = _active_mesh()
    if mesh is None:
        return x
    dims = [_resolve(mesh, d, n) for d, n in zip(x.shape, logical)]
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*dims)))


def constrain_attention_scores(logits):
    """(B, KV, G, Sq, Skv) score tensor: batch -> data axes; kv-heads ->
    model when divisible, else query-heads, else query-seq (archs whose head
    counts don't divide the TP ways, e.g. whisper's 20 or arctic's 8x7)."""
    mesh = _active_mesh()
    if mesh is None:
        return logits
    B, KV, G, Sq, Skv = logits.shape
    dims = [_resolve(mesh, B, "batch"), None, None, None, None]
    if _resolve(mesh, KV, "heads"):
        dims[1] = _resolve(mesh, KV, "heads")
    elif _resolve(mesh, G, "heads"):
        dims[2] = _resolve(mesh, G, "heads")
    # NOTE(§Perf log): a query-seq fallback (Sq -> model) was measured on the
    # arctic train cell and REGRESSED temp 81.7 -> 280 GB/chip (softmax/AV
    # resharding copies); heads-or-nothing is the better baseline.
    return jax.lax.with_sharding_constraint(
        logits, NamedSharding(mesh, P(*dims)))
