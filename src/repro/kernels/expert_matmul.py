"""Grouped SwiGLU expert matmul over rows sorted by expert, Pallas TPU.

The dropless MoE dispatch sorts its (token, expert) pairs by expert: rows
``[start_e, start_e + n_e)`` of ``x`` go to expert e, and the rows past
the last group (pairs routed to experts held elsewhere) to none.  The
kernel computes ``down(silu(x W_gate) * (x W_up))`` for every row with its
own expert's weights and nothing else, so the work is that of the routed
rows only.

Each group is laid out from a boundary of the row tile ``tm`` (its last
tile zero-padded), so a tile belongs to one expert and an expert with at
most ``tm`` rows reads its weights exactly once.  The grid is (tile, F
block): each step streams one ``tf``-wide block of the tile's expert's
three matrices and accumulates the tile's output.  The grid has a static
number of tiles, ``ceil(M / tm) + E``; the unused ones at its end keep the
last block indices, so they fetch nothing and compute nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def tile_layout(group_sizes: jax.Array, M: int, tm: int):
    """Where each of M sorted rows goes when every group starts on a tile
    boundary: ``(slot (M,), tile_expert (G,), n_tiles (1,))``.  ``slot`` is
    the padded row of each row, ``G * tm`` for rows past the last group;
    ``tile_expert`` the expert of each of the G padded tiles."""
    E = group_sizes.shape[0]
    G = -(-M // tm) + E
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    tiles = -(-sizes // tm)
    tile_end = jnp.cumsum(tiles)
    pad_start = (tile_end - tiles) * tm
    r = jnp.arange(M, dtype=jnp.int32)
    e = jnp.minimum(jnp.searchsorted(ends, r, side="right"), E - 1)
    slot = jnp.where(r < ends[-1], pad_start[e] + r - (ends[e] - sizes[e]),
                     G * tm)
    g = jnp.arange(G, dtype=jnp.int32)
    tile_expert = jnp.minimum(jnp.searchsorted(tile_end, g, side="right"),
                              E - 1).astype(jnp.int32)
    return slot, tile_expert, tile_end[-1:].astype(jnp.int32)


def _kernel(exp_ref, nt_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    g = pl.program_id(0)
    f = pl.program_id(1)
    valid = g < nt_ref[0]

    @pl.when(valid & (f == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(valid)
    def _compute():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
        o_ref[...] += jnp.dot(h, wd_ref[0],
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tm", "tf", "interpret"))
def expert_matmul_pallas(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                         w_down: jax.Array, group_sizes: jax.Array, *,
                         tm: int, tf: int = 128,
                         interpret: bool = False) -> jax.Array:
    """x: (M, d) rows sorted by expert; w_gate / w_up: (E, d, F); w_down:
    (E, F, d); group_sizes: (E,) rows per expert, in order, summing to at
    most M.  Returns (M, d) float32; rows past the last group are zero."""
    M, d = x.shape
    E, _, F = w_gate.shape
    tf = tf if F % tf == 0 else F
    slot, tile_expert, n_tiles = tile_layout(group_sizes, M, tm)
    G, nF = tile_expert.shape[0], F // tf
    xp = jnp.zeros((G * tm, d), x.dtype).at[slot].set(x, mode="drop")

    def last(g, f, nt):
        """The step's (tile, F block); past the used tiles, the last
        used step's, so nothing is fetched again."""
        done = g >= nt[0]
        return (jnp.where(done, jnp.maximum(nt[0] - 1, 0), g),
                jnp.where(done, nF - 1, f))

    def x_map(g, f, exp, nt):
        return (last(g, f, nt)[0], 0)

    def wgu_map(g, f, exp, nt):
        t, ff = last(g, f, nt)
        return (exp[t], 0, ff)

    def wd_map(g, f, exp, nt):
        t, ff = last(g, f, nt)
        return (exp[t], ff, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(G, nF),
        in_specs=[pl.BlockSpec((tm, d), x_map),
                  pl.BlockSpec((1, d, tf), wgu_map),
                  pl.BlockSpec((1, d, tf), wgu_map),
                  pl.BlockSpec((1, tf, d), wd_map)],
        out_specs=pl.BlockSpec((tm, d), x_map),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G * tm, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(tile_expert, n_tiles, xp, w_gate, w_up, w_down)
    return jnp.where((slot < G * tm)[:, None],
                     out[jnp.minimum(slot, G * tm - 1)], 0.0)
