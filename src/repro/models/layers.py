"""Shared pure-JAX building blocks for the model zoo.

All modules follow the functional convention:
    init_*(key, cfg, ...) -> params (pytree of jnp arrays)
    *_apply(params, x, ...) -> output

Parameters are plain nested dicts so they compose with jax.lax.scan
(stacked leading layer axis), pjit shardings, and our checkpoint layer
without any framework dependency.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = Any  # nested dict pytree


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def std_normal(key, shape):
    """Standard normal sample behind an optimization barrier: under ``jit``
    XLA would otherwise fold the caller's scale into the sampler's own
    constants, and jitted and eager inits would differ in the last bit."""
    return jax.lax.optimization_barrier(jax.random.normal(key, shape))


def dense_init(key, in_dim: int, out_dim: int, dtype=jnp.float32, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / np.sqrt(in_dim)
    return (std_normal(key, (in_dim, out_dim)) * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype=jnp.float32):
    return (std_normal(key, (vocab, dim)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def init_rmsnorm(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype=dtype)}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dtype)


def init_layernorm(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype=dtype), "bias": jnp.zeros((dim,), dtype=dtype)}


def layernorm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(dtype)


def make_norm(kind: str):
    if kind == "rmsnorm":
        return init_rmsnorm, rmsnorm
    if kind == "layernorm":
        return init_layernorm, layernorm
    raise ValueError(f"unknown norm {kind}")


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    freqs = rope_frequencies(x.shape[-1], theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Grouped-query attention
# ---------------------------------------------------------------------------

def init_attention(key, d_model: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, qkv_bias: bool = False, dtype=jnp.float32) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, d_model, num_heads * head_dim, dtype),
        "wk": dense_init(kk, d_model, num_kv_heads * head_dim, dtype),
        "wv": dense_init(kv, d_model, num_kv_heads * head_dim, dtype),
        "wo": dense_init(ko, num_heads * head_dim, d_model, dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((num_heads * head_dim,), dtype=dtype)
        p["bk"] = jnp.zeros((num_kv_heads * head_dim,), dtype=dtype)
        p["bv"] = jnp.zeros((num_kv_heads * head_dim,), dtype=dtype)
    return p


def _project_qkv(params: Params, x: jax.Array, num_heads: int, num_kv_heads: int,
                 head_dim: int):
    B, S, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, num_heads, head_dim)
    k = k.reshape(B, S, num_kv_heads, head_dim)
    v = v.reshape(B, S, num_kv_heads, head_dim)
    return q, k, v


def gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  mask: jax.Array | None, scale: float | None = None) -> jax.Array:
    """Grouped-query attention core (XLA path).

    q: (B, Sq, H, D); k, v: (B, Skv, KV, D); mask broadcastable to
    (B, KV, G, Sq, Skv) or None.  Returns (B, Sq, H, D).
    """
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    qg = q.reshape(B, Sq, KV, G, D)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    # keep the O(S^2) score tensor sharded: batch over data axes, kv-heads
    # (or query-heads / query-seq fallback) over model; no-op without a mesh
    from repro.distributed.sharding import constrain_attention_scores
    logits = constrain_attention_scores(logits)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, Sq, H, D)


def update_kv_cache(cache: jax.Array, new: jax.Array, offset) -> jax.Array:
    """Write ``new`` (B, T, KV, D) into ``cache`` (B, S, KV, D) at ``offset``.

    ``offset`` may be a scalar (all rows aligned: prefill) or per-row (B,)
    (decode / speculative verification with heterogeneous prefix lengths —
    lowered by XLA to a scatter).
    """
    new = new.astype(cache.dtype)
    offset = jnp.asarray(offset)
    if offset.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(cache, new, offset, axis=1)
    zero = jnp.zeros((), jnp.int32)
    return jax.vmap(
        lambda c, n, o: jax.lax.dynamic_update_slice(c, n, (o, zero, zero))
    )(cache, new, offset.astype(jnp.int32))


def paged_update_kv_cache(pool: jax.Array, new: jax.Array, offset,
                          page_table: jax.Array) -> jax.Array:
    """Write ``new`` (B, T, KV, D) into a page pool (P, ps, KV, D).

    Logical position ``p`` of row ``b`` lives at physical page
    ``page_table[b, p // ps]``, in-page slot ``p % ps``.  Writes through
    unmapped table entries (-1) or past the table width are dropped — that is
    exactly the contract frozen/retired engine rows rely on (their stale
    window writes either land in slack slots that the row's next live round
    overwrites, or vanish).

    Two other layers lean on the same drop semantics: the page allocator's
    device table maps unknown streams to an all-(-1) row, and the engine's
    ``warmup`` traces the jitted draft/verify steps against the REAL pools
    under an all-(-1) table — every write drops, so the donated pool comes
    back bit-identical and can be adopted.  Note the drop bin ``P * ps`` is
    shared by every dropped write, so this scatter must NOT be annotated
    ``unique_indices=True``.
    """
    B, T = new.shape[:2]
    P, ps = pool.shape[:2]
    n_slots = page_table.shape[1]
    offset = jnp.asarray(offset)
    if offset.ndim == 0:
        offset = jnp.broadcast_to(offset, (B,))
    pos = offset[:, None].astype(jnp.int32) + jnp.arange(T, dtype=jnp.int32)
    slot = pos // ps
    phys = jnp.take_along_axis(page_table,
                               jnp.clip(slot, 0, n_slots - 1), axis=1)
    ok = (phys >= 0) & (slot < n_slots)
    flat = jnp.where(ok, phys * ps + pos % ps, P * ps)    # P*ps = drop bin
    flat_pool = pool.reshape((P * ps,) + pool.shape[2:])
    flat_pool = flat_pool.at[flat.reshape(-1)].set(
        new.astype(pool.dtype).reshape((B * T,) + new.shape[2:]), mode="drop")
    return flat_pool.reshape(pool.shape)


def paged_gather_kv(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """Materialize the logical (B, n_slots * ps, KV, D) view of a page pool.

    Unmapped slots (-1) clamp to page 0; callers mask them by position (the
    valid prefix of a stream is always fully mapped).  This is the exact XLA
    reference path — the Pallas ``paged_attention`` kernel streams the same
    tiles through the page table without materializing the view.
    """
    B, n_slots = page_table.shape
    ps = pool.shape[1]
    gathered = pool[jnp.maximum(page_table, 0)]     # (B, n_slots, ps, KV, D)
    return gathered.reshape((B, n_slots * ps) + pool.shape[2:])


def gather_kv_window(leaf: jax.Array, pos: jax.Array,
                     page_table: jax.Array | None = None) -> jax.Array:
    """Gather K/V rows at logical positions ``pos`` (B, T) from a stacked
    cache leaf: (Ln, B, S, KV, D) contiguous, or (Ln, P, ps, KV, D) pools
    with ``page_table`` (B, n_slots).  Out-of-range / unmapped positions
    clamp to a valid slot — callers mask by validity (the accepted prefix
    of a live stream is always fully mapped).  Returns (Ln, B, T, KV, D)."""
    if page_table is None:
        S = leaf.shape[2]
        idx = jnp.clip(pos, 0, S - 1)[None, :, :, None, None]
        return jnp.take_along_axis(leaf, idx, axis=2)
    P, ps = leaf.shape[1], leaf.shape[2]
    n_slots = page_table.shape[1]
    slot = jnp.clip(pos // ps, 0, n_slots - 1)
    phys = jnp.take_along_axis(page_table, slot, axis=1)          # (B, T)
    flat = jnp.maximum(phys, 0) * ps + pos % ps
    flat_leaf = leaf.reshape(leaf.shape[:1] + (P * ps,) + leaf.shape[3:])
    return flat_leaf[:, flat]


def scatter_kv_window(leaf: jax.Array, values: jax.Array, pos: jax.Array,
                      valid: jax.Array,
                      page_table: jax.Array | None = None) -> jax.Array:
    """Write ``values`` (Ln, B, T, KV, D) into a stacked cache leaf at
    logical positions ``pos`` (B, T) where ``valid`` (B, T); invalid,
    out-of-range, and unmapped positions are dropped (the same drop-bin
    contract as ``paged_update_kv_cache``).  This is the K/V scatter-commit
    primitive: the engine moves the accepted tree branch's already-computed
    K/V into its committed slots instead of re-forwarding the path."""
    values = values.astype(leaf.dtype)
    if page_table is None:
        S = leaf.shape[2]
        idx = jnp.where(valid, pos, S)                            # S = drop
        b = jnp.arange(leaf.shape[1])[:, None]
        return leaf.at[:, b, idx].set(values, mode="drop")
    P, ps = leaf.shape[1], leaf.shape[2]
    n_slots = page_table.shape[1]
    slot = pos // ps
    phys = jnp.take_along_axis(page_table,
                               jnp.clip(slot, 0, n_slots - 1), axis=1)
    ok = valid & (phys >= 0) & (slot < n_slots)
    flat = jnp.where(ok, phys * ps + pos % ps, P * ps)            # drop bin
    flat_leaf = leaf.reshape(leaf.shape[:1] + (P * ps,) + leaf.shape[3:])
    flat_leaf = flat_leaf.at[:, flat].set(values, mode="drop")
    return flat_leaf.reshape(leaf.shape)


def causal_mask(Sq: int, Skv: int, offset: int = 0) -> jax.Array:
    """(1, 1, 1, Sq, Skv) boolean mask: query i attends to kv j <= i+offset."""
    qi = jnp.arange(Sq)[:, None] + offset
    kj = jnp.arange(Skv)[None, :]
    return (kj <= qi)[None, None, None]


def tree_window_mask(pos: jax.Array, window_mask: jax.Array,
                     S_max: int) -> jax.Array:
    """(B, 1, 1, T, S_max) attention mask for a token-tree verification
    window written at cache SLOTS [pos_b, pos_b + T).

    ``window_mask`` (B, T, T) is the tree's ancestor-or-self matrix: query
    row t attends every committed slot [0, pos_b) plus window slot t' iff
    ``window_mask[b, t, t']``.  With a lower-triangular matrix this equals
    the plain causal window mask bit-for-bit (the sequential special case).
    """
    B, T = window_mask.shape[:2]
    kj = jnp.arange(S_max)
    committed = kj[None, None, :] < pos[:, None, None]            # (B, 1, S)
    w = kj[None, :] - pos[:, None]                                # (B, S)
    in_win = (w >= 0) & (w < T)
    idx = jnp.broadcast_to(jnp.clip(w, 0, T - 1)[:, None, :], (B, T, S_max))
    allow = jnp.take_along_axis(window_mask, idx, axis=2)         # (B, T, S)
    return (committed | (allow & in_win[:, None, :]))[:, None, None]


def attention_apply(params: Params, x: jax.Array, *, num_heads: int,
                    num_kv_heads: int, head_dim: int, positions: jax.Array,
                    mask: jax.Array | None, rope_theta: float | None,
                    kv_cache: tuple[jax.Array, jax.Array] | None = None,
                    cache_offset: jax.Array | int | None = None,
                    page_table: jax.Array | None = None,
                    window_mask: jax.Array | None = None,
                    causal_window: bool = False):
    """Full attention layer. If kv_cache=(k_cache, v_cache) is given, new keys
    and values are written at ``cache_offset`` and attention runs over the
    whole cache (decode / chunked-prefill path). Returns (out, (k, v)) where
    (k, v) is the updated cache (or the fresh keys/values when no cache).

    With ``page_table`` (B, n_slots), ``kv_cache`` holds page POOLS
    (P, ps, KV, D): writes route through the table.

    Kernel dispatch (the serving hot path): when ``kernel_mode()`` is
    ``pallas``/``interpret`` and the call is a cache window — sequential
    (``causal_window=True``: query row t attends [0, cache_offset + t]) or
    token-tree (``window_mask`` (B, T, T) ancestor-or-self, window written
    at slots [cache_offset, cache_offset + T)) — attention runs through
    ``ops.paged_attention`` / ``ops.tree_attention`` /
    ``ops.paged_tree_attention`` directly on the cache layout.  On the
    paged path this skips the per-layer ``paged_gather_kv``
    materialization of the (B, n_slots * ps, KV, D) logical view entirely.
    ``REPRO_KERNELS=ref`` (the CPU default) keeps the gather + masked
    ``gqa_attention`` jnp path, which the kernel tests assert parity
    against; callers always pass ``mask`` so the fallback never depends on
    the dispatch flags."""
    from repro.kernels import ops as kops

    B, S, _ = x.shape
    q, k, v = _project_qkv(params, x, num_heads, num_kv_heads, head_dim)
    if rope_theta is not None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    if kv_cache is None:
        out = gqa_attention(q, k, v, mask)
        out = out.reshape(B, S, num_heads * head_dim) @ params["wo"]
        return out, (k, v)

    k_cache, v_cache = kv_cache
    dispatch = (kops.kernel_mode() in ("pallas", "interpret")
                and (window_mask is not None or causal_window))
    if page_table is not None:
        k_cache = paged_update_kv_cache(k_cache, k, cache_offset, page_table)
        v_cache = paged_update_kv_cache(v_cache, v, cache_offset, page_table)
        if dispatch:
            lengths = jnp.broadcast_to(jnp.asarray(cache_offset), (B,))
            if window_mask is not None:
                ctx = kops.paged_tree_attention(q, k_cache, v_cache,
                                                page_table, lengths,
                                                window_mask)
            else:
                ctx = kops.paged_attention(q, k_cache, v_cache, page_table,
                                           lengths + 1)
        else:
            kg = paged_gather_kv(k_cache, page_table)
            vg = paged_gather_kv(v_cache, page_table)
            ctx = gqa_attention(q, kg, vg, mask)
    else:
        k_cache = update_kv_cache(k_cache, k, cache_offset)
        v_cache = update_kv_cache(v_cache, v, cache_offset)
        if dispatch and window_mask is not None:
            lengths = jnp.broadcast_to(jnp.asarray(cache_offset), (B,))
            ctx = kops.tree_attention(q, k_cache, v_cache, lengths,
                                      window_mask)
        else:
            # contiguous sequential windows have no materialization to skip:
            # the cache IS the attention operand, so the jnp path stays.
            ctx = gqa_attention(q, k_cache, v_cache, mask)
    out = ctx.reshape(B, S, num_heads * head_dim) @ params["wo"]
    return out, (k_cache, v_cache)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / GELU)
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, activation: str, dtype=jnp.float32) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    if activation in ("swiglu", "geglu"):
        return {"w_gate": dense_init(k1, d_model, d_ff, dtype),
                "w_up": dense_init(k2, d_model, d_ff, dtype),
                "w_down": dense_init(k3, d_ff, d_model, dtype)}
    return {"w_up": dense_init(k1, d_model, d_ff, dtype),
            "w_down": dense_init(k2, d_ff, d_model, dtype)}


def mlp_apply(params: Params, x: jax.Array, activation: str) -> jax.Array:
    if activation == "swiglu":
        return (jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])) @ params["w_down"]
    if activation == "geglu":
        return (jax.nn.gelu(x @ params["w_gate"], approximate=True)
                * (x @ params["w_up"])) @ params["w_down"]
    if activation == "gelu":
        return jax.nn.gelu(x @ params["w_up"], approximate=True) @ params["w_down"]
    raise ValueError(f"unknown activation {activation}")


# ---------------------------------------------------------------------------
# Mixture of Experts (sort-based dispatch with capacity)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff: int                      # per-expert hidden
    activation: str = "swiglu"
    capacity_factor: float = 1.25
    num_shared_experts: int = 0    # always-on shared experts (DeepSeek style)
    shared_d_ff: int = 0
    dense_residual: bool = False   # Arctic-style parallel dense MLP
    dense_d_ff: int = 0


def init_moe(key, d_model: int, mcfg: MoEConfig, dtype=jnp.float32) -> Params:
    keys = jax.random.split(key, 6)
    E, F = mcfg.num_experts, mcfg.d_ff
    scale = 1.0 / np.sqrt(d_model)
    p = {
        "router": dense_init(keys[0], d_model, E, dtype=jnp.float32),
        "w_gate": (std_normal(keys[1], (E, d_model, F)) * scale).astype(dtype),
        "w_up": (std_normal(keys[2], (E, d_model, F)) * scale).astype(dtype),
        "w_down": (std_normal(keys[3], (E, F, d_model)) * (1.0 / np.sqrt(F))).astype(dtype),
    }
    if mcfg.num_shared_experts > 0:
        p["shared"] = init_mlp(keys[4], d_model,
                               mcfg.shared_d_ff or F * mcfg.num_shared_experts,
                               mcfg.activation, dtype)
    if mcfg.dense_residual:
        p["dense"] = init_mlp(keys[5], d_model, mcfg.dense_d_ff or F,
                              mcfg.activation, dtype)
    return p


def moe_apply(params: Params, x: jax.Array, mcfg: MoEConfig,
              capacity_factor: float | None = None) -> tuple[jax.Array, jax.Array]:
    """Sort-based top-k MoE dispatch (capacity-dropped, GShard-style).

    x: (B, S, d). Returns (out, aux_loss) where aux_loss is the load-balancing
    loss of Switch Transformers.
    """
    B, S, d = x.shape
    E, K = mcfg.num_experts, mcfg.top_k
    cf = capacity_factor if capacity_factor is not None else mcfg.capacity_factor
    T = B * S
    xt = x.reshape(T, d)

    logits = (xt.astype(jnp.float32) @ params["router"])        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)             # (T, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Load-balancing aux loss (Switch eq. 4).
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)

    # --- sort-based dispatch, gather-only on the (.., d) tensors ---
    # SPMD partitions gathers far better than scatters (a scatter into a
    # sharded (E*C, d) buffer makes GSPMD replicate one-hot u32 machinery of
    # the same size); the scatters below touch only O(E*C) int32/bool rows.
    C = int(np.ceil(T * K / E * cf))
    flat_expert = expert_idx.reshape(-1)                        # (T*K,)
    flat_token = jnp.repeat(jnp.arange(T), K)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_gate = flat_gate[order]
    # position of each routed item within its expert's run
    first_occurrence = jnp.searchsorted(sorted_expert, sorted_expert, side="left")
    pos_in_expert = jnp.arange(T * K) - first_occurrence
    keep = pos_in_expert < C
    slot = jnp.where(keep, sorted_expert * C + pos_in_expert, E * C)  # E*C = drop bin

    # NOTE(§Perf log): three dispatch variants were measured on the arctic
    # train cell — plain scatter (81.7 GB/chip), scatter+expert-constraint
    # (113.6 GB), gather-only+constraint (280 GB).  GSPMD replicates scatter
    # one-hot machinery either way; plain scatter without constraints is the
    # best current baseline, ragged/shard_map dispatch is future work.
    buf = jnp.zeros((E * C + 1, d), dtype=x.dtype).at[slot].set(xt[sorted_token])
    buf = buf[:-1].reshape(E, C, d)

    if mcfg.activation in ("swiglu", "geglu"):
        act = jax.nn.silu if mcfg.activation == "swiglu" else jax.nn.gelu
        h = act(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])) \
            * jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, params["w_up"]))
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"]).reshape(E * C, d)
    out_buf = jnp.concatenate([out_buf, jnp.zeros((1, d), out_buf.dtype)], axis=0)

    contrib = out_buf[slot] * (sorted_gate * keep)[:, None].astype(x.dtype)
    yt = jax.ops.segment_sum(contrib, sorted_token, num_segments=T)

    y = yt.reshape(B, S, d)
    if "shared" in params:
        y = y + mlp_apply(params["shared"], x, mcfg.activation)
    if "dense" in params:
        y = y + mlp_apply(params["dense"], x, mcfg.activation)
    return y, aux


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2/V3 MLA, no query compression)
# ---------------------------------------------------------------------------

def init_mla(key, d_model: int, num_heads: int, kv_lora_rank: int,
             qk_nope: int, qk_rope: int, v_dim: int,
             dtype=jnp.float32) -> Params:
    """``wq`` (d, H * (nope + rope)); ``wkv_a`` (d, rank + rope), the latent
    and the shared rope key; ``kv_norm`` on the latent; ``wkv_b``
    (rank, H * (nope + v)), per head the key's no-rope part then the value;
    ``wo`` (H * v, d)."""
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, d_model, num_heads * (qk_nope + qk_rope), dtype),
        "wkv_a": dense_init(ka, d_model, kv_lora_rank + qk_rope, dtype),
        "kv_norm": init_rmsnorm(kv_lora_rank, dtype),
        "wkv_b": dense_init(kb, kv_lora_rank,
                            num_heads * (qk_nope + v_dim), dtype),
        "wo": dense_init(ko, num_heads * v_dim, d_model, dtype),
    }


def rope_deinterleaved(x: jax.Array, positions: jax.Array,
                       theta: float) -> jax.Array:
    """RoPE as DeepSeek-V3 applies it: the rope dims are de-interleaved
    (even dims first, then odd) and then rotated half against half."""
    D = x.shape[-1]
    perm = np.concatenate([np.arange(0, D, 2), np.arange(1, D, 2)])
    return apply_rope(x[..., perm], positions, theta)


def mla_project(params: Params, x: jax.Array, *, num_heads: int,
                qk_nope: int, positions: jax.Array, rope_theta: float):
    """Queries and latent rows of x (B, S, d): q_nope (B, S, H, nope), the
    roped q_rope (B, S, H, rope), and the cached latent rows (B, S, rank +
    rope): the normed latent, then the roped key shared by every head.
    The latent's norm takes eps 1e-6, the published modeling code's default
    for ``kv_a_layernorm`` (the blocks' norms take the config's)."""
    B, S, _ = x.shape
    q = (x @ params["wq"]).reshape(B, S, num_heads, -1)
    q_nope = q[..., :qk_nope]
    q_rope = rope_deinterleaved(q[..., qk_nope:], positions, rope_theta)
    kv = x @ params["wkv_a"]
    rank = params["kv_norm"]["scale"].shape[0]
    c_kv = rmsnorm(params["kv_norm"], kv[..., :rank], eps=1e-6)
    k_rope = rope_deinterleaved(kv[..., None, rank:], positions,
                                rope_theta)[:, :, 0]
    return q_nope, q_rope, jnp.concatenate([c_kv, k_rope], axis=-1)


def _mla_up(params: Params, num_heads: int, qk_nope: int):
    """The key (rank, H, nope) and value (rank, H, v) up-projections."""
    rank = params["wkv_b"].shape[0]
    w = params["wkv_b"].reshape(rank, num_heads, -1)
    return w[..., :qk_nope], w[..., qk_nope:]


def mla_expanded(params: Params, q_nope, q_rope, latent, mask, *,
                 num_heads: int, qk_nope: int) -> jax.Array:
    """Attention of S queries over the same S latent rows with K and V
    expanded per head (prefill and full forward).  mask broadcastable to
    (B, H, S, S); returns (B, S, H * v)."""
    B, S = q_nope.shape[:2]
    rank = params["wkv_b"].shape[0]
    kv = (latent[..., :rank] @ params["wkv_b"]).reshape(B, S, num_heads, -1)
    k_nope, v = kv[..., :qk_nope], kv[..., qk_nope:]
    k_rope = jnp.broadcast_to(latent[:, :, None, rank:],
                              (B, S, num_heads, latent.shape[-1] - rank))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope.astype(k_nope.dtype)], axis=-1)
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v).reshape(B, S, -1)


def mla_apply(params: Params, x: jax.Array, *, num_heads: int, qk_nope: int,
              positions: jax.Array, mask: jax.Array | None,
              rope_theta: float, latent_cache: jax.Array | None = None,
              cache_offset: jax.Array | int | None = None,
              page_table: jax.Array | None = None, prefill: bool = False,
              causal_window: bool = False):
    """Multi-head latent attention.  Returns (out, latent) where latent is
    the updated cache leaf, or the fresh latent rows without a cache.

    Without a cache, and for a ``prefill`` (which starts from an empty
    cache: the prompt attends only to itself), attention runs in the
    EXPANDED form: K and V per head from the latent rows.  Windows against
    a cache run in the ABSORBED form: the no-rope query goes through the
    key up-projection into the latent space, the scores are taken against
    the cached latent rows, the values are their latent part, and the
    value up-projection follows.  A paged cache (``page_table``) runs that
    through ``ops.latent_attention`` (the Pallas kernel, or its oracle);
    it takes sequential windows only (``causal_window``).  A contiguous
    cache (B, S, rank + rope) uses ``mask`` (B, 1, 1, T, S)."""
    from repro.kernels import ops as kops

    B, S, _ = x.shape
    q_nope, q_rope, latent = mla_project(
        params, x, num_heads=num_heads, qk_nope=qk_nope, positions=positions,
        rope_theta=rope_theta)
    if latent_cache is None or prefill:
        causal = (mask[:, 0] if latent_cache is None
                  else jnp.tril(jnp.ones((S, S), bool)))
        ctx = mla_expanded(params, q_nope, q_rope, latent, causal,
                           num_heads=num_heads, qk_nope=qk_nope)
        out = ctx @ params["wo"]
        if latent_cache is None:
            return out, latent
    rank = params["wkv_b"].shape[0]
    w_uk, w_uv = _mla_up(params, num_heads, qk_nope)
    new = latent[:, :, None]
    if page_table is not None:
        pool = paged_update_kv_cache(latent_cache[:, :, None], new,
                                     cache_offset, page_table)[:, :, 0]
    else:
        pool = update_kv_cache(latent_cache[:, :, None], new,
                               cache_offset)[:, :, 0]
    if prefill:
        return out, pool
    q = jnp.concatenate([jnp.einsum("bthn,rhn->bthr", q_nope, w_uk),
                         q_rope], axis=-1)
    scale = 1.0 / np.sqrt(q_nope.shape[-1] + q_rope.shape[-1])
    if page_table is not None:
        if not causal_window:
            raise NotImplementedError(
                "a paged latent cache takes sequential windows only: token-"
                "tree windows need a paged tree latent-attention path")
        lengths = jnp.broadcast_to(jnp.asarray(cache_offset), (B,)) + 1
        ctx = kops.latent_attention(q, pool, page_table, lengths, dv=rank,
                                    scale=scale)
    else:
        logits = jnp.einsum("bthc,bsc->bhts", q,
                            pool).astype(jnp.float32) * scale
        logits = jnp.where(mask[:, 0], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(pool.dtype)
        ctx = jnp.einsum("bhts,bsr->bthr", probs, pool[..., :rank])
    ctx = jnp.einsum("bthr,rhv->bthv", ctx.astype(x.dtype), w_uv)
    return ctx.reshape(B, S, -1) @ params["wo"], pool


# ---------------------------------------------------------------------------
# Sigmoid-routed experts, dropless, over the experts held here
# ---------------------------------------------------------------------------

def init_routed_experts(key, d_model: int, *, num_experts: int, held: int,
                        d_ff: int, shared_d_ff: int,
                        dtype=jnp.float32) -> Params:
    """Router over all ``num_experts`` (float32 weights and the float32
    score-correction bias), the weights of the ``held`` experts only, and
    the shared expert (one SwiGLU of width ``shared_d_ff``)."""
    keys = jax.random.split(key, 5)
    scale = 1.0 / np.sqrt(d_model)
    p = {
        "router": dense_init(keys[0], d_model, num_experts, jnp.float32),
        "bias": jnp.zeros((num_experts,), jnp.float32),
        "w_gate": (std_normal(keys[1], (held, d_model, d_ff))
                   * scale).astype(dtype),
        "w_up": (std_normal(keys[2], (held, d_model, d_ff))
                 * scale).astype(dtype),
        "w_down": (std_normal(keys[3], (held, d_ff, d_model))
                   * (1.0 / np.sqrt(d_ff))).astype(dtype),
    }
    if shared_d_ff:
        p["shared"] = init_mlp(keys[4], d_model, shared_d_ff, "swiglu", dtype)
    return p


def route_sigmoid(params: Params, x: jax.Array, top_k: int,
                  routed_scale: float):
    """DeepSeek-V3 ``noaux_tc`` routing with one expert group, in float32:
    sigmoid scores over every expert, the top-k chosen on score plus the
    correction bias, gates the chosen scores without the bias, normalised
    to sum 1 and times ``routed_scale``.  x: (T, d).  Returns (expert
    ids (T, k), gates (T, k) float32)."""
    logits = jnp.matmul(x.astype(jnp.float32), params["router"],
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + params["bias"], top_k)
    gates = jnp.take_along_axis(scores, idx, axis=1)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return idx, gates * routed_scale


def routed_experts_apply(params: Params, x: jax.Array, *, top_k: int,
                         routed_scale: float, first_expert: int = 0):
    """Dropless MoE over the experts held here, plus the shared expert.

    Routes every token over all experts (``route_sigmoid``), keeps the
    (token, expert) pairs whose expert is held here (experts
    ``first_expert`` .. ``first_expert + held - 1``), sorts them by expert
    and runs only those rows through ``ops.expert_matmul``.  The pairs of
    experts held elsewhere contribute nothing here: the result is this
    share's part of the routed sum, plus the shared expert, which every
    share computes alike.  x: (B, S, d).  Returns (y, counts) with counts
    int32 (2,): (token, held expert) pairs computed, and held experts that
    got at least one token."""
    from repro.kernels import ops as kops

    B, S, d = x.shape
    T = B * S
    held = params["w_gate"].shape[0]
    E = params["router"].shape[1]
    xt = x.reshape(T, d)
    idx, gates = route_sigmoid(params, xt, top_k, routed_scale)
    local = idx.reshape(-1) - first_expert
    local = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(local, stable=True)
    sizes = jnp.bincount(local, length=held + 1)[:held].astype(jnp.int32)
    token = order // top_k
    # row tile: about twice an expert's expected rows, so that an expert's
    # weights are read once per pass (16 rows for a 72-token verify window)
    tm = int(np.clip(2 ** np.ceil(np.log2(2 * T * top_k / E)), 16, 128))
    rows = kops.expert_matmul(xt[token], params["w_gate"], params["w_up"],
                              params["w_down"], sizes, tm=tm)
    # rows of pairs held elsewhere come back zero
    rows = rows * gates.reshape(-1)[order][:, None]
    y = jnp.zeros((T, d), jnp.float32).at[token].add(rows)
    y = y.astype(x.dtype).reshape(B, S, d)
    if "shared" in params:
        y = y + mlp_apply(params["shared"], x, "swiglu")
    counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0)]).astype(jnp.int32)
    return y, counts
