"""Paged latent attention (MLA, absorbed form), Pallas TPU.

Multi-head latent attention caches one row per token: the normed latent
``c_kv`` (``kv_lora_rank`` wide) followed by the roped key part
(``qk_rope_head_dim`` wide), shared by every head.  With the key and value
up-projections absorbed into the queries and the output, a head's query is
a row of the same width, its scores are dot products with the cached rows,
and its values are the rows' first ``kv_lora_rank`` columns.  So each
latent page is read once, for the scores and the values of all heads:
every head's T window rows form one (T * H, C) query tile against one
(ps, C) page.

The grid walks the page-table slots of each row like ``paged_attention``
(page table and lengths scalar-prefetched, the page's BlockSpec index
chasing the physical page), with the same online-softmax helpers.  Row t
of the window attends logical positions ``[0, lengths_b + t)``; unmapped
slots (page id -1) are skipped whole.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (head_major, init_softmax, softmax_scratch,
                              softmax_step, token_major)


def _kernel(pt_ref, len_ref, q_ref, kv_ref, o_ref, m_scr, l_scr, acc_scr, *,
            ps: int, n_slots: int, H: int, T: int, dv: int, scale: float):
    b = pl.program_id(0)
    si = pl.program_id(1)
    R = T * H

    @pl.when(si == 0)
    def _init():
        init_softmax(m_scr, l_scr, acc_scr)

    base = len_ref[b]

    @pl.when((pt_ref[b, si] >= 0) & (si * ps < base + T - 1))
    def _update():
        kpos = jax.lax.broadcasted_iota(jnp.int32, (R, ps), 1) + si * ps
        trow = jax.lax.broadcasted_iota(jnp.int32, (R, ps), 0) // H
        rows = kv_ref[0].astype(jnp.float32)             # (ps, C)
        softmax_step(0, q_ref[0, 0].astype(jnp.float32), rows, rows,
                     kpos < base + trow, m_scr, l_scr, acc_scr, scale=scale)

    @pl.when(si == n_slots - 1)
    def _finish():
        out = acc_scr[0, :, :dv] / jnp.maximum(l_scr[0, :, :1], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dv", "scale", "interpret"))
def latent_attention_pallas(q: jax.Array, pool: jax.Array,
                            page_table: jax.Array, lengths: jax.Array, *,
                            dv: int, scale: float,
                            interpret: bool = False) -> jax.Array:
    """q: (B, T, H, C) absorbed queries; pool: (P, ps, C) latent rows;
    page_table: (B, n_slots) int32 (-1 = unmapped); lengths: (B,) valid
    rows for window row 0 (row t attends [0, lengths_b + t)).  Returns
    (B, T, H, dv): the softmax-weighted sum of each row's first ``dv``
    columns."""
    B, T, H, C = q.shape
    P, ps, _ = pool.shape
    n_slots = page_table.shape[1]
    R = T * H

    def q_map(b, si, pt, ln):
        return (b, 0, 0, 0)

    def kv_map(b, si, pt, ln):
        return (jnp.maximum(pt[b, si], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_slots),
        in_specs=[pl.BlockSpec((1, 1, R, C), q_map),
                  pl.BlockSpec((1, ps, C), kv_map)],
        out_specs=pl.BlockSpec((1, 1, R, dv), q_map),
        scratch_shapes=softmax_scratch(1, R, C),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, ps=ps, n_slots=n_slots, H=H, T=T, dv=dv,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, R, dv), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      head_major(q, 1), pool)
    return token_major(out, T)
