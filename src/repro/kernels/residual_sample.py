"""Fused residual-distribution inverse-CDF sampler (paper eq. 5).

The calibrated token at the first rejected position is a sample from
normalize(max(p_L - p_S, 0)) over the vocab.  A naive implementation
materializes the residual, its sum, and its cumsum — three extra HBM sweeps
of (N, V).  This kernel streams the vocab tiles twice within one grid
(phase 0: residual mass Z; phase 1: CDF crossing), carrying the running sum
and the found-token state in VMEM scratch across the sequential TPU grid.

Degenerate rows (Z == 0, i.e. p == q elementwise) fall back to argmax(p),
matching the pure-jnp oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_LANES = 128


def tile_first_crossing(r, prev, target):
    """First column of the (1, bv) non-negative tile ``r`` at which the
    running sum ``prev + cumsum(r)`` exceeds ``target``, or ``bv`` if none.

    The chip's kernel language has no cumsum, so each 128-lane chunk takes
    its inclusive prefix sum as a matmul with an upper-triangular ones
    matrix (at full float32 precision), carried across chunks by the
    chunk's last (largest) value.  Returns ``(first, running sum)``.
    """
    bv = r.shape[1]
    w = min(bv, _LANES)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (w, w), 1))
    tri = tri.astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    first = jnp.int32(bv)
    run = prev
    for c in range(bv // w):
        chunk = r[:, c * w:(c + 1) * w]
        cum = run + jax.lax.dot_general(
            chunk, tri, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        first = jnp.minimum(first, jnp.min(
            jnp.where(cum > target, lane + c * w, bv)))
        run = jnp.max(cum)
    return first, run


def _kernel(u_ref, p_ref, q_ref, out_ref, st_scr, *, bv: int, n_v: int):
    ni = pl.program_id(0)
    phase = pl.program_id(1)
    vi = pl.program_id(2)

    p = p_ref[...].astype(jnp.float32)            # (1, bv)
    q = q_ref[...].astype(jnp.float32)
    r = jnp.maximum(p - q, 0.0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, bv), 1) + vi * bv

    # st_scr (SMEM): 0 Z, 1 running cumsum, 2 picked token, 3 best p,
    # 4 argmax(p) for the degenerate fallback
    @pl.when((phase == 0) & (vi == 0))
    def _init():
        st_scr[0] = 0.0
        st_scr[1] = 0.0
        st_scr[2] = -1.0
        st_scr[3] = -1e30
        st_scr[4] = -1.0

    @pl.when(phase == 0)
    def _accumulate():
        st_scr[0] = st_scr[0] + jnp.sum(r)
        m_tile = jnp.max(p)
        arg_tile = jnp.max(jnp.where(p == m_tile, cols, -1))

        @pl.when(m_tile > st_scr[3])
        def _upd():
            st_scr[3] = m_tile
            st_scr[4] = arg_tile.astype(jnp.float32)

    @pl.when(phase == 1)
    def _pick():
        first, run = tile_first_crossing(r, st_scr[1], u_ref[ni] * st_scr[0])

        @pl.when((first < bv) & (st_scr[2] < 0))
        def _record():
            st_scr[2] = (vi * bv + first).astype(jnp.float32)

        st_scr[1] = run

        @pl.when(vi == n_v - 1)
        def _finish():
            fallback = st_scr[4]
            picked = jnp.where(st_scr[2] < 0, fallback, st_scr[2])
            tok = jnp.where(st_scr[0] <= 0.0, fallback, picked)
            out_ref[...] = jnp.full(out_ref.shape, tok.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("bv", "interpret"))
def residual_sample_pallas(p: jax.Array, q: jax.Array, u: jax.Array,
                           bv: int = 2048, interpret: bool = False) -> jax.Array:
    """p, q: (N, V) probability rows; u: (N,) uniforms -> tokens (N,) int32."""
    N, V = p.shape
    v_pad = (-V) % bv
    if v_pad:
        p = jnp.pad(p, ((0, 0), (0, v_pad)))
        q = jnp.pad(q, ((0, 0), (0, v_pad)))
    n_v = p.shape[1] // bv
    # rows carry a unit middle dim, squeezed out of the block: the chip's
    # tiling then sees (1, bv) / (1, 1) blocks that span that whole dim
    tile = lambda ni, ph, vi: (ni, 0, vi)

    out = pl.pallas_call(
        functools.partial(_kernel, bv=bv, n_v=n_v),
        grid=(N, 2, n_v),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # u
            pl.BlockSpec((None, 1, bv), tile),
            pl.BlockSpec((None, 1, bv), tile),
        ],
        out_specs=pl.BlockSpec((None, 1, 1), lambda ni, ph, vi: (ni, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1, 1), jnp.int32),
        scratch_shapes=[pltpu.SMEM((8,), jnp.float32)],
        interpret=interpret,
    )(u.astype(jnp.float32), p[:, None], q[:, None])
    return out[:, 0, 0]
