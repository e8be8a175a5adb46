"""Decoder-only transformer LM (dense / MoE / VLM-backbone).

One unified forward covers all serving modes:

  * ``apply``        — full causal forward (training / scoring), no cache
  * ``prefill``      — forward that also writes the KV cache
  * ``forward_window`` — T new tokens against an existing cache at per-row
    offsets: T=1 is decode, T=L+1 is batched speculative verification (the
    paper's server-side op)

Layers are stacked on a leading axis and traversed with ``jax.lax.scan`` so
the lowered HLO stays O(1) in depth (fast multi-pod compiles, clean remat).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

from repro.distributed.sharding import logical_constraint

from .layers import (
    MoEConfig,
    attention_apply,
    dense_init,
    embed_init,
    init_attention,
    init_mla,
    init_mlp,
    init_moe,
    init_routed_experts,
    make_norm,
    mla_apply,
    mlp_apply,
    moe_apply,
    routed_experts_apply,
    tree_window_mask,
)

Params = Any


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


def strip_view(cache: Params) -> Params:
    """Drop the ``"pages"`` page-table entry from a paged cache view,
    leaving only the pool leaves.  The serving engine adopts the cache a
    forward returns and must never retain the table inside a cache it later
    passes to a DONATING jitted step: the page allocator's device mirror
    owns the live table, and a stale copy riding in the cache would either
    leak or alias a donated buffer.  No-op for contiguous caches."""
    if "pages" not in cache:
        return cache
    return {k: v for k, v in cache.items() if k != "pages"}


class DecoderLM:
    """Functional decoder-only LM parameterized by ``ModelConfig``."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.init_norm, self.norm = make_norm(cfg.norm)
        if cfg.norm_eps is not None:
            self.norm = functools.partial(self.norm, eps=cfg.norm_eps)
        # multi-head latent attention: one latent cache leaf per layer stack
        self.mla = cfg.attention == "mla"
        # sigmoid-routed dropless experts held here: the blocks' auxiliary
        # output is the routing counts (pairs, experts touched), not a loss
        self.routed = bool(cfg.num_experts) and cfg.router == "sigmoid"
        if self.routed != (self.mla and bool(cfg.num_experts)):
            raise NotImplementedError("sigmoid-routed experts come with MLA "
                                      "blocks, and MLA blocks take no others")
        self.moe_cfg = None
        if cfg.num_experts:
            self.moe_cfg = MoEConfig(
                num_experts=cfg.num_experts, top_k=cfg.top_k, d_ff=cfg.moe_d_ff,
                activation=cfg.activation, capacity_factor=cfg.capacity_factor,
                num_shared_experts=cfg.num_shared_experts,
                shared_d_ff=cfg.shared_d_ff, dense_residual=cfg.dense_residual,
                dense_d_ff=cfg.d_ff,
            )

    @property
    def no_drop_capacity(self) -> float:
        """Capacity factor at which dropping is impossible (C = T tokens):
        cf = E / k since C = ceil(T k / E * cf)."""
        assert self.moe_cfg is not None
        return self.moe_cfg.num_experts / self.moe_cfg.top_k

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------

    def _init_block(self, key, moe: bool) -> Params:
        cfg = self.cfg
        dtype = _dtype(cfg.param_dtype)
        k_attn, k_mlp = jax.random.split(key)
        if self.mla:
            attn = init_mla(k_attn, cfg.d_model, cfg.num_heads,
                            cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                            cfg.qk_rope_head_dim, cfg.v_head_dim, dtype)
        else:
            attn = init_attention(k_attn, cfg.d_model, cfg.num_heads,
                                  cfg.num_kv_heads, cfg.head_dim,
                                  qkv_bias=cfg.qkv_bias, dtype=dtype)
        p = {
            "ln_attn": self.init_norm(cfg.d_model, dtype),
            "attn": attn,
            "ln_mlp": self.init_norm(cfg.d_model, dtype),
        }
        if moe and self.routed:
            p["moe"] = init_routed_experts(
                k_mlp, cfg.d_model, num_experts=cfg.num_experts,
                held=cfg.num_experts_held, d_ff=cfg.moe_d_ff,
                shared_d_ff=cfg.shared_d_ff, dtype=dtype)
        elif moe:
            p["moe"] = init_moe(k_mlp, cfg.d_model, self.moe_cfg, dtype)
        else:
            p["mlp"] = init_mlp(k_mlp, cfg.d_model, cfg.d_ff, cfg.activation, dtype)
        return p

    def init(self, key) -> Params:
        cfg = self.cfg
        dtype = _dtype(cfg.param_dtype)
        k_embed, k_blocks, k_head, k_extra = jax.random.split(key, 4)
        n_dense = cfg.first_k_dense if cfg.num_experts else cfg.num_layers
        n_moe = cfg.num_layers - n_dense if cfg.num_experts else 0
        if not cfg.num_experts:
            n_dense, n_moe = 0, 0  # all layers homogeneous, stacked below

        params: Params = {
            "embed": embed_init(k_embed, cfg.vocab_size, cfg.d_model, dtype),
            "ln_f": self.init_norm(cfg.d_model, dtype),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = dense_init(k_head, cfg.d_model, cfg.vocab_size, dtype)

        # ``lax.map`` writes each layer straight into the stacked leaves, so
        # no per-layer copy is ever live beside the stack
        if cfg.num_experts:
            if n_dense:
                keys = jax.random.split(k_extra, n_dense)
                params["dense_blocks"] = jax.lax.map(
                    lambda k: self._init_block(k, moe=False), keys)
            keys = jax.random.split(k_blocks, n_moe)
            params["blocks"] = jax.lax.map(
                lambda k: self._init_block(k, moe=True), keys)
        else:
            keys = jax.random.split(k_blocks, cfg.num_layers)
            params["blocks"] = jax.lax.map(
                lambda k: self._init_block(k, moe=False), keys)
        return params

    # ------------------------------------------------------------------
    # Cache
    # ------------------------------------------------------------------

    def _latent_cache(self, lead: tuple, dtype) -> Params:
        """MLA cache leaves: one latent row per position, (layers,) + lead
        + (rank + rope,), for the scanned and the leading dense stacks."""
        cfg = self.cfg
        n_dense = cfg.first_k_dense if cfg.num_experts else 0
        shape = lead + (cfg.latent_dim,)
        cache = {"latent": jnp.zeros((cfg.num_layers - n_dense,) + shape,
                                     dtype)}
        if n_dense:
            cache["dense_latent"] = jnp.zeros((n_dense,) + shape, dtype)
        return cache

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> Params:
        cfg = self.cfg
        if self.mla:
            return self._latent_cache((batch, max_len), dtype)
        n_scan = cfg.num_layers - (cfg.first_k_dense if cfg.num_experts else 0)
        n_dense = cfg.first_k_dense if cfg.num_experts else 0
        shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
        cache = {"k": jnp.zeros((n_scan,) + shape, dtype),
                 "v": jnp.zeros((n_scan,) + shape, dtype)}
        if n_dense:
            cache["dense_k"] = jnp.zeros((n_dense,) + shape, dtype)
            cache["dense_v"] = jnp.zeros((n_dense,) + shape, dtype)
        return cache

    def cache_spec(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return jax.eval_shape(lambda: self.init_cache(batch, max_len, dtype))

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.bfloat16) -> Params:
        """Paged KV pool: every attention leaf is (layers, P, ps, KV, D) with
        NO batch axis — streams own pages, not rows.  Pair it with a
        ``"pages"`` (B, n_slots) int32 page table (``serving.PagedKVCache``)
        to form a per-batch cache view accepted by ``prefill`` /
        ``forward_window``.  An MLA model's pool holds one latent row per
        position instead: (layers, P, ps, rank + rope)."""
        cfg = self.cfg
        if self.mla:
            return self._latent_cache((num_pages, page_size), dtype)
        n_scan = cfg.num_layers - (cfg.first_k_dense if cfg.num_experts else 0)
        n_dense = cfg.first_k_dense if cfg.num_experts else 0
        shape = (num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
        cache = {"k": jnp.zeros((n_scan,) + shape, dtype),
                 "v": jnp.zeros((n_scan,) + shape, dtype)}
        if n_dense:
            cache["dense_k"] = jnp.zeros((n_dense,) + shape, dtype)
            cache["dense_v"] = jnp.zeros((n_dense,) + shape, dtype)
        return cache

    CACHE_BATCH_AXES = {"k": 1, "v": 1, "dense_k": 1, "dense_v": 1,
                        "latent": 1, "dense_latent": 1}

    strip_view = staticmethod(strip_view)

    @staticmethod
    def _cache_kv_capacity(cache: Params) -> int:
        """Logical KV positions per row: S for contiguous (B, S, KV, D)
        leaves, n_slots * page_size for a paged view."""
        leaf = cache["latent" if "latent" in cache else "k"]
        if "pages" in cache:
            return cache["pages"].shape[1] * leaf.shape[2]
        return leaf.shape[2]

    def concat_caches(self, caches: list) -> Params:
        """Stack per-row caches (ragged prefill) into one batch."""
        return {key: jnp.concatenate([c[key] for c in caches],
                                     axis=self.CACHE_BATCH_AXES[key])
                for key in caches[0]}

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------

    def _block_apply(self, p: Params, x, *, moe: bool, positions, mask,
                     kv_cache=None, offset=None, moe_capacity=None,
                     page_table=None, window_mask=None, causal_window=False,
                     prefill=False):
        cfg = self.cfg
        if self.mla:
            return self._mla_block_apply(
                p, x, moe=moe, positions=positions, mask=mask,
                kv_cache=kv_cache, offset=offset, page_table=page_table,
                causal_window=causal_window, prefill=prefill)
        h = self.norm(p["ln_attn"], x)
        attn_out, kv = attention_apply(
            p["attn"], h, num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, positions=positions, mask=mask,
            rope_theta=cfg.rope_theta, kv_cache=kv_cache, cache_offset=offset,
            page_table=page_table, window_mask=window_mask,
            causal_window=causal_window)
        x = x + attn_out
        h = self.norm(p["ln_mlp"], x)
        if moe:
            mlp_out, aux = moe_apply(p["moe"], h, self.moe_cfg,
                                     capacity_factor=moe_capacity)
        else:
            mlp_out, aux = mlp_apply(p["mlp"], h, cfg.activation), jnp.zeros((), jnp.float32)
        return x + mlp_out, kv, aux

    def _mla_block_apply(self, p: Params, x, *, moe: bool, positions, mask,
                         kv_cache, offset, page_table, causal_window,
                         prefill):
        """An MLA block; its cache is the 1-tuple ``(latent,)``.  The
        auxiliary output is the routing counts of sigmoid-routed experts
        (int32 (2,), zeros in a dense block), or a zero loss in a model
        without experts.  Token-tree windows (not ``causal_window``) over a
        paged latent cache are refused in ``mla_apply``."""
        cfg = self.cfg
        h = self.norm(p["ln_attn"], x)
        with jax.named_scope("mla"):
            attn_out, latent = mla_apply(
                p["attn"], h, num_heads=cfg.num_heads,
                qk_nope=cfg.qk_nope_head_dim, positions=positions, mask=mask,
                rope_theta=cfg.rope_theta,
                latent_cache=None if kv_cache is None else kv_cache[0],
                cache_offset=offset, page_table=page_table, prefill=prefill,
                causal_window=causal_window)
        x = x + attn_out
        h = self.norm(p["ln_mlp"], x)
        if moe:
            with jax.named_scope("moe"):
                mlp_out, aux = routed_experts_apply(
                    p["moe"], h, top_k=cfg.top_k,
                    routed_scale=cfg.routed_scale,
                    first_expert=cfg.first_expert)
        else:
            mlp_out, aux = mlp_apply(p["mlp"], h, cfg.activation), self._aux0()
        return x + mlp_out, (latent,), aux

    def _aux0(self):
        """A block's auxiliary output when it has none to give."""
        if self.routed:
            return jnp.zeros((2,), jnp.int32)
        return jnp.zeros((), jnp.float32)

    def _stack_forward(self, params, x, positions, mask, cache=None, offset=None,
                       moe_capacity=None, window_mask=None,
                       causal_window=False, prefill=False):
        """Run all blocks; returns (hidden, new_cache, aux_sum).  aux_sum
        is the load-balancing loss, or for sigmoid-routed experts the
        routing counts (routed pairs, experts touched) summed over
        layers."""
        cfg = self.cfg
        use_cache = cache is not None
        page_table = cache.get("pages") if use_cache else None

        def block_fn(p, x, kv_in):
            # positions/mask/offset/page_table are closure-captured: they
            # carry no gradient, and jax.checkpoint must not trace the
            # python-bool configuration kwargs.
            return self._block_apply(p, x, moe=self.moe_cfg is not None,
                                     positions=positions, mask=mask,
                                     kv_cache=kv_in, offset=offset,
                                     moe_capacity=moe_capacity,
                                     page_table=page_table,
                                     window_mask=window_mask,
                                     causal_window=causal_window,
                                     prefill=prefill)

        if cfg.remat:
            block_fn = jax.checkpoint(block_fn)

        new_cache = dict(cache) if use_cache else None
        aux_total = self._aux0()
        leaves = ("latent",) if self.mla else ("k", "v")
        stacks = [("blocks", leaves, block_fn)]
        if "dense_blocks" in params:
            # leading dense blocks (MoE stacks with first_k_dense > 0)
            def dense_fn(p, x, kv_in):
                return self._block_apply(
                    p, x, moe=False, positions=positions, mask=mask,
                    kv_cache=kv_in, offset=offset, page_table=page_table,
                    window_mask=window_mask, causal_window=causal_window,
                    prefill=prefill)
            stacks.insert(0, ("dense_blocks",
                              tuple("dense_" + k for k in leaves), dense_fn))

        for blocks, names, fn in stacks:
            if use_cache:
                x, new_leaves, aux = _scan_with_cache(
                    fn, x, params[blocks], tuple(cache[k] for k in names),
                    cfg.scan_unroll)
                new_cache.update(zip(names, new_leaves))
            else:
                def body(x, p, fn=fn):
                    x, _, aux = fn(p, x, None)
                    return x, aux
                x, aux = jax.lax.scan(body, x, params[blocks],
                                      unroll=cfg.scan_unroll)
            aux_total += jnp.sum(aux, axis=0)
        return x, new_cache, aux_total

    def _embed(self, params, tokens, prefix_embeds=None):
        cfg = self.cfg
        x = params["embed"][tokens].astype(_dtype(cfg.compute_dtype))
        if prefix_embeds is not None:
            x = jnp.concatenate(
                [prefix_embeds.astype(x.dtype), x], axis=1)
        return x

    def _logits(self, params, x):
        cfg = self.cfg
        x = self.norm(params["ln_f"], x)
        w = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
        logits = (x @ w.astype(x.dtype)).astype(jnp.float32)
        return logical_constraint(logits, "batch", None, "vocab")

    def apply(self, params, tokens, prefix_embeds=None, moe_capacity=None):
        """Full causal forward. tokens: (B, S) -> logits (B, S[+P], V)."""
        x = self._embed(params, tokens, prefix_embeds)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        qi = jnp.arange(S)[:, None]
        kj = jnp.arange(S)[None, :]
        mask = (kj <= qi)[None, None, None]
        x, _, aux = self._stack_forward(params, x, positions, mask,
                                        moe_capacity=moe_capacity)
        if self.routed:
            # bias-corrected routing (``noaux_tc``) has no auxiliary loss
            aux = jnp.zeros((), jnp.float32)
        return self._logits(params, x), aux

    def prefill(self, params, tokens, cache, prefix_embeds=None,
                moe_capacity="no_drop"):
        """Causal forward writing the KV cache at offset 0.

        MoE dispatch defaults to exact no-drop capacity: serving prefill
        batches are modest (K devices x prompt) and the cache must reflect
        the exact model for verification to stay exact.  Pass an explicit
        capacity factor for throughput-oriented bulk prefill.  The third
        value is the blocks' auxiliary output (``_stack_forward``): for
        sigmoid-routed experts, the pass's routing counts.
        """
        if moe_capacity == "no_drop":
            moe_capacity = self.no_drop_capacity if self.moe_cfg else None
        x = self._embed(params, tokens, prefix_embeds)
        B, S, _ = x.shape
        S_max = self._cache_kv_capacity(cache)
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        qi = jnp.arange(S)[:, None]
        kj = jnp.arange(S_max)[None, :]
        mask = (kj <= qi)[None, None, None]
        offset = jnp.zeros((), jnp.int32)
        # prefill IS a sequential window at offset 0 (row t attends
        # [0, t]), so the paged-attention kernel path applies
        x, cache, aux = self._stack_forward(params, x, positions, mask,
                                            cache=cache, offset=offset,
                                            moe_capacity=moe_capacity,
                                            causal_window=True,
                                            prefill=self.mla)
        return self._logits(params, x), cache, aux

    def forward_window(self, params, tokens, cache, pos, window_mask=None,
                       window_depth=None, return_aux=False):
        """T new tokens against an existing cache.

        tokens: (B, T); pos: (B,) per-row write offsets (current lengths).
        T=1 -> decode step; T=L+1 -> speculative-verification scoring.
        Returns (logits (B, T, V), new_cache).

        ``cache`` is either the contiguous layout (``init_cache``) or a paged
        view: ``init_paged_cache`` pools plus a ``"pages"`` (B, n_slots)
        page table — writes route through the table, numerics are identical.

        Token-TREE windows (multi-draft verification) pass ``window_mask``
        (B, T, T), the tree's ancestor-or-self matrix, and ``window_depth``
        (B, T) node depths: window token t keeps cache SLOT pos + t but
        takes rope position pos + depth_t and attends committed KV plus its
        in-window ancestors only.  Defaults (causal / arange) reproduce the
        sequential window bit-for-bit.

        MoE layers dispatch with NO-DROP capacity here (cf = E/k => capacity =
        num window tokens): speculative verification must score with the exact
        model distribution, and capacity dropping is batch-coupled.  Training
        and prefill keep the configured capacity factor (DESIGN.md §3).

        ``return_aux`` adds the blocks' auxiliary output as a third value
        (for sigmoid-routed experts: the pass's routing counts).
        """
        x = self._embed(params, tokens)
        B, T, _ = x.shape
        S_max = self._cache_kv_capacity(cache)
        if window_depth is None:
            positions = pos[:, None] + jnp.arange(T)[None, :]
        else:
            positions = pos[:, None] + window_depth
        if window_mask is None:
            kj = jnp.arange(S_max)[None, None, :]
            mask = (kj <= positions[:, :, None])[:, None, None]  # (B,1,1,T,S)
        else:
            mask = tree_window_mask(pos, window_mask, S_max)
        moe_capacity = self.no_drop_capacity if self.moe_cfg else None
        # ``causal_window`` marks the standard sequential window (positions
        # pos + t): a window_depth WITHOUT a window_mask would put rope
        # positions out of step with the kernels' row-index mask, so it is
        # excluded from kernel dispatch.
        x, cache, aux = self._stack_forward(
            params, x, positions, mask, cache=cache, offset=pos,
            moe_capacity=moe_capacity, window_mask=window_mask,
            causal_window=window_mask is None and window_depth is None)
        if return_aux:
            return self._logits(params, x), cache, aux
        return self._logits(params, x), cache

    def num_params(self, params) -> int:
        return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def _scan_with_cache(layer_fn, x, blocks, leaves, unroll):
    """Scan ``layer_fn(p, x, kv) -> (x, kv, aux)`` over stacked layers with
    the stacked cache ``leaves`` (a tuple, layers first: (k, v), or the MLA
    latent alone) in the CARRY: each layer reads its slices and writes them
    back in place.  Scanned over as inputs instead, the caches would come
    back as a new stacked output, a fresh copy of every pool per window
    pass."""

    def body(carry, xs):
        x, *stacked = carry
        p, i = xs
        kv_in = tuple(jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
                      for a in stacked)
        x, kv, aux = layer_fn(p, x, kv_in)
        stacked = [jax.lax.dynamic_update_index_in_dim(a, n, i, 0)
                   for a, n in zip(stacked, kv)]
        return (x, *stacked), aux

    layers = jnp.arange(leaves[0].shape[0], dtype=jnp.int32)
    (x, *leaves), aux = jax.lax.scan(body, (x, *leaves), (blocks, layers),
                                     unroll=unroll)
    return x, tuple(leaves), aux


def _stack(trees: list[Params]) -> Params:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
