"""Token-tree verification attention (SpecInfer-style multi-draft), Pallas TPU.

The engine scores a whole token tree — J root-divergent drafts packed into a
prefix-deduplicated trie — in ONE target pass: the T-token window (pending
token + tree nodes) is written into the KV cache at consecutive SLOTS
``[lengths_b, lengths_b + T)`` while each node's rope position is its tree
DEPTH, and attention is masked so a node sees (a) every committed slot and
(b) exactly its in-window ancestors (``win_mask``, the ancestor-or-self
matrix of the tree).  A lower-triangular ``win_mask`` makes this kernel
bit-compatible with the sequential verification window.

Two layouts, matching the cache layouts of ``SpecEngine``:

  * contiguous — caches are (B, S, KV, D) slabs; the kernel walks S tiles
    with the online-softmax state in VMEM scratch (``decode_attention``
    pattern).
  * paged      — caches are (P, ps, KV, D) pools addressed through a
    scalar-prefetched page table, so each KV tile is DMA'd straight from
    its physical page (``paged_attention`` pattern); unmapped slots are
    skipped whole.

The in-window ancestor test is evaluated on the MXU as a one-hot matmul
(mask-row x slot-one-hot) instead of a gather, which keeps the kernel free
of dynamic indexing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (
    finish_softmax,
    head_major,
    init_softmax,
    softmax_scratch,
    token_major,
    update_softmax,
)
_DOT_1_1 = (((1,), (1,)), ((), ()))
_DOT_1_0 = (((1,), (0,)), ((), ()))


def _win_allow(mask_f, base, tile0, tile_w, T, gsize):
    """(T * gsize, tile_w) float: 1.0 where query row r (= t * gsize + g) may
    attend the window node living at slot ``tile0 + column``.

    ``mask_f`` is the (T, T) ancestor matrix as float32.  Row -> node and
    slot -> node membership are both resolved by one-hot matmuls, so the
    kernel needs no gather and no reshape: column c holds window node
    ``tile0 + c - base`` when that lands in [0, T).
    """
    R = T * gsize
    rrow = jax.lax.broadcasted_iota(jnp.int32, (R, T), 0) // gsize
    rcol = jax.lax.broadcasted_iota(jnp.int32, (R, T), 1)
    rowsel = (rrow == rcol).astype(jnp.float32)  # (R, T)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (tile_w, T), 0) + tile0
    tcol = jax.lax.broadcasted_iota(jnp.int32, (tile_w, T), 1)
    onehot = (kcol - base == tcol).astype(jnp.float32)  # (tile_w, T)
    mask_rows = jax.lax.dot_general(rowsel, mask_f, _DOT_1_0, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(mask_rows, onehot, _DOT_1_1, preferred_element_type=jnp.float32)


def _update(q_ref, k_ref, v_ref, mask_ref, m_scr, l_scr, acc_scr, *, base, tile0, tile_w, T, gsize, n_kv, scale):
    """Attend one K/V tile: committed slots plus in-window ancestors."""
    kpos = jax.lax.broadcasted_iota(jnp.int32, (T * gsize, tile_w), 1) + tile0
    allow = _win_allow(mask_ref[0].astype(jnp.float32), base, tile0, tile_w, T, gsize)
    update_softmax(q_ref, k_ref, v_ref, (kpos < base) | (allow > 0.5), m_scr, l_scr, acc_scr, n_kv=n_kv, scale=scale)


def _kernel(len_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr, *, bs, n_s, T, gsize, n_kv, scale):
    b = pl.program_id(0)
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        init_softmax(m_scr, l_scr, acc_scr)

    base = len_ref[b]

    # tiles wholly past the window horizon contribute nothing
    @pl.when(si * bs < base + T)
    def _tile():
        _update(q_ref, k_ref, v_ref, mask_ref, m_scr, l_scr, acc_scr, base=base, tile0=si * bs, tile_w=bs, T=T, gsize=gsize, n_kv=n_kv, scale=scale)

    @pl.when(si == n_s - 1)
    def _finish():
        finish_softmax(o_ref, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def tree_attention_pallas(q, k_cache, v_cache, lengths, win_mask, bs=128, interpret=False):
    """q: (B, T, H, D); caches: (B, S, KV, D) with the window already written
    at slots [lengths_b, lengths_b + T); lengths: (B,); win_mask: (B, T, T)
    bool ancestor-or-self matrix.  Returns (B, T, H, D).

    Grid: (B, S-tiles); a K/V block is one S tile with every kv head, so
    each tile is streamed once per row.  All T window rows x G = H/KV query
    heads of one kv head share a (T*G, D) q tile (the GQA + tree-window
    bandwidth win).
    """
    B, T, H, D = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    R = T * (H // KV)
    bs = min(bs, S)
    s_pad = (-S) % bs
    if s_pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
    n_s = k_cache.shape[1] // bs

    kernel = functools.partial(_kernel, bs=bs, n_s=n_s, T=T, gsize=H // KV, n_kv=KV, scale=1.0 / np.sqrt(D))
    out = pl.pallas_call(
        kernel,
        grid=(B, n_s),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # lengths
            pl.BlockSpec((1, KV, R, D), lambda b, si: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, KV, D), lambda b, si: (b, si, 0, 0)),
            pl.BlockSpec((1, bs, KV, D), lambda b, si: (b, si, 0, 0)),
            pl.BlockSpec((1, T, T), lambda b, si: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, R, D), lambda b, si: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, R, D), q.dtype),
        scratch_shapes=softmax_scratch(KV, R, D),
        interpret=interpret,
    )(lengths.astype(jnp.int32), head_major(q, KV), k_cache, v_cache, win_mask.astype(jnp.int32))
    return token_major(out, T)


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr, *, ps, n_slots, T, gsize, n_kv, scale):
    b = pl.program_id(0)
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        init_softmax(m_scr, l_scr, acc_scr)

    base = len_ref[b]

    # unmapped slots or slots wholly past the window horizon skip the DMA
    @pl.when((pt_ref[b, si] >= 0) & (si * ps < base + T))
    def _tile():
        _update(q_ref, k_ref, v_ref, mask_ref, m_scr, l_scr, acc_scr, base=base, tile0=si * ps, tile_w=ps, T=T, gsize=gsize, n_kv=n_kv, scale=scale)

    @pl.when(si == n_slots - 1)
    def _finish():
        finish_softmax(o_ref, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_tree_attention_pallas(q, k_pool, v_pool, page_table, lengths, win_mask, interpret=False):
    """``tree_attention_pallas`` through a paged KV cache.

    q: (B, T, H, D); pools: (P, ps, KV, D); page_table: (B, n_slots) int32
    (-1 = unmapped); lengths: (B,); win_mask: (B, T, T) bool.  The page
    table and lengths are scalar-prefetched so the k/v index maps resolve
    slot -> physical page before each DMA, exactly like ``paged_attention``.
    """
    B, T, H, D = q.shape
    ps, KV = k_pool.shape[1], k_pool.shape[2]
    n_slots = page_table.shape[1]
    R = T * (H // KV)

    def q_map(b, si, pt, ln):
        return (b, 0, 0, 0)

    def kv_map(b, si, pt, ln):
        return (jnp.maximum(pt[b, si], 0), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_slots),
        in_specs=[
            pl.BlockSpec((1, KV, R, D), q_map),
            pl.BlockSpec((1, ps, KV, D), kv_map),
            pl.BlockSpec((1, ps, KV, D), kv_map),
            pl.BlockSpec((1, T, T), lambda b, si, pt, ln: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, R, D), q_map),
        scratch_shapes=softmax_scratch(KV, R, D),
    )
    kernel = functools.partial(_paged_kernel, ps=ps, n_slots=n_slots, T=T, gsize=H // KV, n_kv=KV, scale=1.0 / np.sqrt(D))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, R, D), q.dtype),
        interpret=interpret,
    )(
        page_table.astype(jnp.int32),
        lengths.astype(jnp.int32),
        head_major(q, KV),
        k_pool,
        v_pool,
        win_mask.astype(jnp.int32),
    )
    return token_major(out, T)
