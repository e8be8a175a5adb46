"""Fused draft-verification + calibrated-sampling Pallas kernel.

The verify hot path ran THREE dispatches per round: ``gather_softmax_prob``
over the (B*L, V) drafted-position logits, the jnp accept-test/cumprod, and
``residual_sample`` over dense (B, V) rows — the middle one forcing the
dense residual distribution (softmax + sparse-q scatter) to materialize in
HBM between the other two.  This kernel does the whole chain in one
``pallas_call`` per batch row, streaming the vocab tiles three times within
one sequential grid:

  phase 0  online softmax max/denominator for every drafted position plus
           the drafted token's logit; at the last tile run the accept test
           ``u < min(1, p_L/p_S)``, the prefix-acceptance count, and record
           the first-rejected row ``sel`` and its softmax stats.
  phase 1  residual mass Z_r = sum max(p_sel - q_sel, 0), rebuilding the
           sparse SLM row (idx, val) tile-locally, plus the argmax(p)
           degenerate fallback.
  phase 2  inverse-CDF crossing of u_resid * Z_r -> calibrated token.

Uniforms are drawn by the caller (``core.verification.verify_drafts``) with
the unchanged key splits, so the committed tokens are distributed exactly as
the unfused path.  The bonus token on full acceptance stays outside.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .residual_sample import tile_first_crossing

_NEG = -1e30


def _kernel(dlen_ref, u_res_ref, tok_ids_ref, probs_ref, u_acc_ref,
            logits_ref, qidx_ref, qval_ref,
            acc_ref, nacc_ref, out_ref,
            m_scr, z_scr, pick_scr, st_scr,
            *, L: int, Lr: int, bv: int, n_v: int):
    b = pl.program_id(0)
    phase = pl.program_id(1)
    vi = pl.program_id(2)

    logits = logits_ref[0].astype(jnp.float32)              # (Lr, bv)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Lr, bv), 1) + vi * bv
    rows = jax.lax.broadcasted_iota(jnp.int32, (Lr, 1), 0)

    # st_scr (SMEM): 0 sel, 1 m_sel, 2 z_sel, 3 Z_r, 4 running cumsum,
    # 5 picked token, 6 best p, 7 argmax(p) for the degenerate fallback

    # ---- phase 0: online softmax stats + picked logit per drafted row ----
    @pl.when((phase == 0) & (vi == 0))
    def _init0():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        z_scr[...] = jnp.zeros_like(z_scr)
        pick_scr[...] = jnp.full_like(pick_scr, _NEG)

    @pl.when(phase == 0)
    def _stats():
        m_prev = m_scr[:, :1]                               # (Lr, 1)
        m_tile = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_tile)
        corr = jnp.exp(m_prev - m_new)
        z_new = z_scr[:, :1] * corr + jnp.sum(
            jnp.exp(logits - m_new), axis=-1, keepdims=True)

        hit = cols == tok_ids_ref[...]                      # (Lr, bv)
        picked_tile = jnp.max(jnp.where(hit, logits, _NEG), axis=-1,
                              keepdims=True)
        pick_new = jnp.maximum(pick_scr[:, :1], picked_tile)

        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        z_scr[...] = jnp.broadcast_to(z_new, z_scr.shape)
        pick_scr[...] = jnp.broadcast_to(pick_new, pick_scr.shape)

        @pl.when(vi == n_v - 1)
        def _accept():
            p_t = jnp.exp(pick_new - m_new) / z_new          # (Lr, 1)
            ratio = p_t / jnp.maximum(probs_ref[...], 1e-30)
            live = rows < dlen_ref[b]
            acc = (u_acc_ref[...] < jnp.minimum(ratio, 1.0)) & live
            # prefix-acceptance count = first rejected row (padded rows
            # never accept, so one always exists)
            n_acc = jnp.min(jnp.where(acc, Lr, rows))
            acc_ref[...] = acc.astype(jnp.int32)
            nacc_ref[...] = jnp.full(nacc_ref.shape, n_acc)
            sel = jnp.minimum(n_acc, L - 1)
            is_sel = rows == sel
            st_scr[0] = sel.astype(jnp.float32)
            st_scr[1] = jnp.sum(jnp.where(is_sel, m_new, 0.0))
            st_scr[2] = jnp.sum(jnp.where(is_sel, z_new, 0.0))

    def _residual_tile():
        """max(p_sel - q_sel, 0) over this vocab tile, plus p_sel itself."""
        sel = st_scr[0].astype(jnp.int32)
        is_sel = rows == sel                                 # (Lr, 1)
        l_sel = jnp.sum(jnp.where(is_sel, logits, 0.0), axis=0,
                        keepdims=True)                       # (1, bv)
        p = jnp.exp(l_sel - st_scr[1]) / st_scr[2]
        # the sparse SLM row of ``sel`` as (Vhat, 1) columns
        sel_col = jax.lax.broadcasted_iota(jnp.int32, (1, Lr), 1) == sel
        idx_sel = jnp.sum(jnp.where(sel_col, qidx_ref[0], 0), axis=1,
                          keepdims=True)
        val_sel = jnp.sum(jnp.where(sel_col, qval_ref[0].astype(jnp.float32),
                                    0.0), axis=1, keepdims=True)
        q = jnp.sum(jnp.where(idx_sel == cols[:1], val_sel, 0.0), axis=0,
                    keepdims=True)                           # (1, bv)
        return p, jnp.maximum(p - q, 0.0)

    # ---- phase 1: residual mass + argmax(p) fallback ----
    @pl.when((phase == 1) & (vi == 0))
    def _init1():
        st_scr[3] = 0.0
        st_scr[4] = 0.0
        st_scr[5] = -1.0
        st_scr[6] = _NEG
        st_scr[7] = -1.0

    @pl.when(phase == 1)
    def _mass():
        p, r = _residual_tile()
        st_scr[3] = st_scr[3] + jnp.sum(r)
        m_tile = jnp.max(p)
        arg_tile = jnp.max(jnp.where(p == m_tile, cols[:1], -1))

        @pl.when(m_tile > st_scr[6])
        def _upd():
            st_scr[6] = m_tile
            st_scr[7] = arg_tile.astype(jnp.float32)

    # ---- phase 2: inverse-CDF crossing ----
    @pl.when(phase == 2)
    def _pick():
        _, r = _residual_tile()
        first, run = tile_first_crossing(r, st_scr[4],
                                         u_res_ref[b] * st_scr[3])

        @pl.when((first < bv) & (st_scr[5] < 0))
        def _record():
            st_scr[5] = (vi * bv + first).astype(jnp.float32)

        st_scr[4] = run

        @pl.when(vi == n_v - 1)
        def _finish():
            fallback = st_scr[7]
            picked = jnp.where(st_scr[5] < 0, fallback, st_scr[5])
            tok = jnp.where(st_scr[3] <= 0.0, fallback, picked)
            out_ref[...] = jnp.full(out_ref.shape, tok.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("bv", "interpret"))
def fused_verify_sample_pallas(target_logits: jax.Array, draft_tokens: jax.Array,
                               draft_probs: jax.Array, q_idx: jax.Array,
                               q_val: jax.Array, u_accept: jax.Array,
                               u_resid: jax.Array, draft_len: jax.Array,
                               bv: int = 2048, interpret: bool = False):
    """See ``ref.fused_verify_sample_ref`` for shapes and semantics."""
    B, L = draft_tokens.shape
    V = target_logits.shape[-1]
    Vhat = q_idx.shape[-1]

    logits = target_logits[:, :L]                            # (B, L, V)
    Lr = -(-L // 8) * 8
    l_pad = Lr - L
    v_pad = (-V) % bv
    if l_pad or v_pad:
        logits = jnp.pad(logits, ((0, 0), (0, l_pad), (0, v_pad)),
                         constant_values=_NEG)
        draft_tokens = jnp.pad(draft_tokens, ((0, 0), (0, l_pad)))
        draft_probs = jnp.pad(draft_probs, ((0, 0), (0, l_pad)),
                              constant_values=1.0)
        u_accept = jnp.pad(u_accept, ((0, 0), (0, l_pad)), constant_values=1.0)
        q_idx = jnp.pad(q_idx, ((0, 0), (0, l_pad), (0, 0)))
        q_val = jnp.pad(q_val, ((0, 0), (0, l_pad), (0, 0)))
    n_v = logits.shape[-1] // bv

    # per-position operands are (Lr, 1) columns, matching the row stats of
    # the logits tile; the sparse SLM rows are (Vhat, Lr), so the one row
    # that is selected comes out as a (Vhat, 1) column
    row = lambda b, ph, vi: (b, 0, 0)
    per_pos = pl.BlockSpec((None, Lr, 1), row)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    acc, nacc, tok = pl.pallas_call(
        functools.partial(_kernel, L=L, Lr=Lr, bv=bv, n_v=n_v),
        grid=(B, 3, n_v),
        in_specs=[
            smem,                                                 # draft_len
            smem,                                                 # u_resid
            per_pos,                                              # tokens
            per_pos,                                              # p_S
            per_pos,                                              # u_accept
            pl.BlockSpec((1, Lr, bv), lambda b, ph, vi: (b, 0, vi)),
            pl.BlockSpec((1, Vhat, Lr), row),
            pl.BlockSpec((1, Vhat, Lr), row),
        ],
        out_specs=[per_pos, pl.BlockSpec((None, 1, 1), row),
                   pl.BlockSpec((None, 1, 1), row)],
        out_shape=[
            jax.ShapeDtypeStruct((B, Lr, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Lr, 128), jnp.float32),   # online max
            pltpu.VMEM((Lr, 128), jnp.float32),   # online denominator
            pltpu.VMEM((Lr, 128), jnp.float32),   # picked logit
            pltpu.SMEM((8,), jnp.float32),        # per-row scalar state
        ],
        interpret=interpret,
    )(jnp.minimum(draft_len, L).astype(jnp.int32),
      u_resid.astype(jnp.float32),
      draft_tokens.astype(jnp.int32)[..., None], draft_probs[..., None],
      u_accept[..., None], logits,
      q_idx.astype(jnp.int32).transpose(0, 2, 1), q_val.transpose(0, 2, 1))
    return acc[:, :L, 0].astype(bool), nacc[:, 0, 0], tok[:, 0, 0]
