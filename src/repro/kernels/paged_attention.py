"""Paged decode/window attention: queries against a paged KV cache, Pallas TPU.

The serving engine's KV lives in fixed-size pages of a preallocated pool
(``serving.kv_cache.PagedKVCache``); a per-stream page table maps logical
positions to physical pages.  The kernel walks the page-table SLOTS of each
row in grid order and lets the BlockSpec index map chase the physical page:
the page table rides in SMEM via scalar prefetch, so the pipeline DMAs each
KV tile HBM->VMEM directly from its physical page — the logical view is
never materialized (the XLA reference path gathers it instead).

Masking is per query row: row ``t`` of a T-token window attends logical
positions ``[0, lengths_b + t)`` (T=1 is plain decode); unmapped slots
(page id -1) are skipped whole.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def head_major(q, n_kv: int):
    """(B, T, H, D) -> (B, KV, T * G, D): the T window rows x G = H/KV query
    heads of one kv head form one (T * G, D) tile."""
    B, T, H, D = q.shape
    G = H // n_kv
    return (q.reshape(B, T, n_kv, G, D).transpose(0, 2, 1, 3, 4)
            .reshape(B, n_kv, T * G, D))


def token_major(out, T: int):
    """Inverse of ``head_major``."""
    B, KV, R, D = out.shape
    G = R // T
    return (out.reshape(B, KV, T, G, D).transpose(0, 2, 1, 3, 4)
            .reshape(B, T, KV * G, D))


def softmax_scratch(n_kv: int, R: int, D: int):
    """Online-softmax state per kv head: running max, denominator, output."""
    return [pltpu.VMEM((n_kv, R, 128), jnp.float32),
            pltpu.VMEM((n_kv, R, 128), jnp.float32),
            pltpu.VMEM((n_kv, R, D), jnp.float32)]


def init_softmax(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, _NEG)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def update_softmax(q_ref, k_ref, v_ref, visible, m_scr, l_scr, acc_scr, *,
                   n_kv: int, scale: float):
    """One online-softmax step of every kv head over one K/V tile.

    The tile holds all kv heads, ``(1, w, KV, D)`` — the chip's tiling needs
    the head dim whole — so each tile is read once for every head; queries
    are ``head_major``, ``(1, KV, R, D)``; ``visible`` is the (R, w) mask.
    """
    for h in range(n_kv):
        q = q_ref[0, h].astype(jnp.float32)              # (R, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)        # (w, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        softmax_step(h, q, k, v, visible, m_scr, l_scr, acc_scr, scale=scale)


def softmax_step(h: int, q, k, v, visible, m_scr, l_scr, acc_scr, *,
                 scale: float):
    """One online-softmax step of state slot ``h``: queries q (R, D)
    against keys k (w, D) and values v (w, Dv), already loaded as float32;
    ``visible`` is the (R, w) mask."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(visible, s, _NEG)
    m_prev = m_scr[h, :, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[h, :, :1] = (l_scr[h, :, :1] * corr
                       + jnp.sum(p, axis=-1, keepdims=True))
    m_scr[h, :, :1] = m_new
    acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def finish_softmax(o_ref, l_scr, acc_scr):
    out = acc_scr[...] / jnp.maximum(l_scr[:, :, :1], 1e-30)
    o_ref[0] = out.astype(o_ref.dtype)


def _kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, ps: int, n_slots: int, n_kv: int, gsize: int,
            T: int, scale: float):
    b = pl.program_id(0)
    si = pl.program_id(1)
    R = T * gsize                                        # query rows / head

    @pl.when(si == 0)
    def _init():
        init_softmax(m_scr, l_scr, acc_scr)

    base = len_ref[b]

    # the LAST query row sees the most positions; slots past its horizon or
    # unmapped slots contribute nothing and are skipped whole
    @pl.when((pt_ref[b, si] >= 0) & (si * ps < base + T - 1))
    def _update():
        kpos = jax.lax.broadcasted_iota(jnp.int32, (R, ps), 1) + si * ps
        trow = jax.lax.broadcasted_iota(jnp.int32, (R, ps), 0) // gsize
        update_softmax(q_ref, k_ref, v_ref, kpos < base + trow, m_scr, l_scr,
                       acc_scr, n_kv=n_kv, scale=scale)

    @pl.when(si == n_slots - 1)
    def _finish():
        finish_softmax(o_ref, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_pallas(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           page_table: jax.Array, lengths: jax.Array,
                           interpret: bool = False) -> jax.Array:
    """q: (B, T, H, D); pools: (P, ps, KV, D); page_table: (B, n_slots)
    int32 (-1 = unmapped); lengths: (B,) valid kv count for query row 0
    (row t attends [0, lengths_b + t)).  Returns (B, T, H, D).

    Grid: (B, n_slots); the page table and lengths are scalar-prefetched
    so the k/v index maps resolve slot -> physical page before each DMA.
    A K/V block is one whole physical page with every kv head, streamed
    once per row.
    """
    B, T, H, D = q.shape
    P, ps, KV, _ = k_pool.shape
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
        ],
        out_specs=pl.BlockSpec((1, KV, R, D), q_map),
        scratch_shapes=softmax_scratch(KV, R, D),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, ps=ps, n_slots=n_slots, n_kv=KV,
                          gsize=H // KV, T=T, scale=1.0 / np.sqrt(D)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, R, D), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      head_major(q, KV), k_pool, v_pool)
    return token_major(out, T)
