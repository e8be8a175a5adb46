"""Jit'd public wrappers around the Pallas kernels.

Each op dispatches between the Pallas TPU kernel and the pure-jnp oracle in
``ref.py``:  on CPU (this container) the oracle executes; on TPU the Pallas
path is used; ``interpret=True`` Pallas execution is exercised by the kernel
tests.  The environment variable / flag ``REPRO_KERNELS`` ∈
{auto, pallas, ref, interpret} forces a path.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.obs import trace

from . import ref

_MODE_ENV = "REPRO_KERNELS"


def kernel_mode() -> str:
    mode = os.environ.get(_MODE_ENV, "auto")
    if mode == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return mode


def _use_pallas() -> bool:
    return kernel_mode() in ("pallas", "interpret")


def _interpret() -> bool:
    return kernel_mode() == "interpret"


def _span(name: str, x):
    """Dispatch span for one op: name + lead-operand shape/dtype + the
    backend actually dispatched (pallas | interpret | ref).  The args dict
    is only built when a tracer is installed, so untraced dispatch pays a
    single function call (``trace.NULL_SPAN``) and nothing else.  Span
    durations measure DISPATCH wall time (under ``jit``: trace time); the
    device's time is in a profiler trace (``Tracer(profiler=True)``)."""
    if trace.active() is None:
        return trace.NULL_SPAN
    backend = ("interpret" if _interpret()
               else "pallas" if _use_pallas() else "ref")
    return trace.span(name, cat="kernel",
                      args={"shape": list(x.shape), "dtype": str(x.dtype),
                            "backend": backend})


# ---------------------------------------------------------------------------
# SSD (Mamba-2)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk",))
def _ssd_scan_ref_jit(x, dt, A, B, C, chunk, initial_state):
    return ref.ssd_scan_ref(x, dt, A, B, C, chunk=chunk, initial_state=initial_state)


def ssd_scan(x, dt, A, B, C, chunk: int = 64, initial_state=None):
    """Chunked SSD forward. See ref.ssd_scan_ref for shapes.

    Sequences are zero-padded to a chunk multiple; dt=0 padding is exact
    (decay e^0 = 1, update 0), so the final state is untouched.
    """
    s = x.shape[1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    with _span("ops.ssd_scan", x):
        if _use_pallas():
            from .ssd_scan import ssd_scan_pallas
            y, fs = ssd_scan_pallas(x, dt, A, B, C, chunk=chunk,
                                    initial_state=initial_state,
                                    interpret=_interpret())
        else:
            y, fs = ref.ssd_scan_ref(x, dt, A, B, C, chunk=chunk,
                                     initial_state=initial_state)
    return (y[:, :s] if pad else y), fs


def ssd_decode(x, dt, A, B, C, state):
    """One-token SSD recurrence (cheap; always the jnp formulation)."""
    with _span("ops.ssd_decode", x):
        out = ref.ssd_decode_ref(x, dt, A, B, C, state)
    return out


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, causal: bool = True):
    """Causal GQA attention. q: (B,Sq,H,D), k/v: (B,Skv,KV,D)."""
    with _span("ops.flash_attention", q):
        if _use_pallas():
            from .flash_attention import flash_attention_pallas
            out = flash_attention_pallas(q, k, v, causal=causal,
                                         interpret=_interpret())
        else:
            out = ref.flash_attention_ref(q, k, v, causal=causal)
    return out


def decode_attention(q, k_cache, v_cache, lengths):
    """Single-step decode attention against a KV cache."""
    with _span("ops.decode_attention", q):
        if _use_pallas():
            from .decode_attention import decode_attention_pallas
            out = decode_attention_pallas(q, k_cache, v_cache, lengths,
                                          interpret=_interpret())
        else:
            out = ref.decode_attention_ref(q, k_cache, v_cache, lengths)
    return out


def paged_attention(q, k_pool, v_pool, page_table, lengths):
    """Window/decode attention through a paged KV cache.

    q: (B, T, H, D); pools: (P, ps, KV, D); page_table: (B, n_slots) int32
    (-1 = unmapped); lengths: (B,) valid kv count for query row 0 (row t
    attends [0, lengths + t)).
    """
    with _span("ops.paged_attention", q):
        if _use_pallas():
            from .paged_attention import paged_attention_pallas
            out = paged_attention_pallas(q, k_pool, v_pool, page_table,
                                         lengths, interpret=_interpret())
        else:
            out = ref.paged_attention_ref(q, k_pool, v_pool, page_table,
                                          lengths)
    return out


def latent_attention(q, pool, page_table, lengths, *, dv: int,
                     scale: float):
    """Absorbed multi-head latent attention through a paged latent pool.

    q: (B, T, H, C) latent-space queries; pool: (P, ps, C) latent rows;
    page_table: (B, n_slots) int32 (-1 = unmapped); lengths: (B,) valid
    rows for window row 0 (row t attends [0, lengths + t)).  Values are
    each row's first ``dv`` columns; returns (B, T, H, dv).
    """
    with _span("ops.latent_attention", q):
        if _use_pallas():
            from .latent_attention import latent_attention_pallas
            out = latent_attention_pallas(q, pool, page_table, lengths,
                                          dv=dv, scale=scale,
                                          interpret=_interpret())
        else:
            out = ref.latent_attention_ref(q, pool, page_table, lengths,
                                           dv=dv, scale=scale)
    return out


def tree_attention(q, k_cache, v_cache, lengths, win_mask):
    """Token-tree verification window over a contiguous KV cache.

    q: (B, T, H, D) tree window (slot 0 = pending token); caches:
    (B, S, KV, D) with the window already written at slots
    [lengths, lengths + T); win_mask: (B, T, T) ancestor-or-self matrix.
    A lower-triangular win_mask recovers the sequential causal window.
    """
    with _span("ops.tree_attention", q):
        if _use_pallas():
            from .tree_attention import tree_attention_pallas
            out = tree_attention_pallas(q, k_cache, v_cache, lengths,
                                        win_mask, interpret=_interpret())
        else:
            out = ref.tree_attention_ref(q, k_cache, v_cache, lengths,
                                         win_mask)
    return out


def paged_tree_attention(q, k_pool, v_pool, page_table, lengths, win_mask):
    """``tree_attention`` through a paged KV cache (scalar-prefetched page
    table; pools (P, ps, KV, D), page_table (B, n_slots), -1 = unmapped)."""
    with _span("ops.paged_tree_attention", q):
        if _use_pallas():
            from .tree_attention import paged_tree_attention_pallas
            out = paged_tree_attention_pallas(q, k_pool, v_pool, page_table,
                                              lengths, win_mask,
                                              interpret=_interpret())
        else:
            out = ref.paged_tree_attention_ref(q, k_pool, v_pool, page_table,
                                               lengths, win_mask)
    return out


def decode_attention_q8(q, k_cache, v_cache, k_scale, v_scale, lengths):
    """Decode attention over an int8 KV cache (per-head scales)."""
    with _span("ops.decode_attention_q8", q):
        if _use_pallas():
            from .decode_attention import decode_attention_q8_pallas
            out = decode_attention_q8_pallas(q, k_cache, v_cache, k_scale,
                                             v_scale, lengths,
                                             interpret=_interpret())
        else:
            out = ref.decode_attention_quantized_ref(q, k_cache, v_cache,
                                                     k_scale, v_scale,
                                                     lengths)
    return out


# ---------------------------------------------------------------------------
# Mixture of experts
# ---------------------------------------------------------------------------

def expert_matmul(x, w_gate, w_up, w_down, group_sizes, *, tm: int):
    """SwiGLU experts over rows sorted by expert (dropless dispatch).

    x: (M, d); w_gate / w_up: (E, d, F); w_down: (E, F, d); group_sizes:
    (E,) rows per expert in order, summing to at most M.  ``tm`` is the
    kernel's row tile.  Returns (M, d) float32; rows past the last group
    (pairs of experts held elsewhere) are zero.
    """
    with _span("ops.expert_matmul", x):
        if _use_pallas():
            from .expert_matmul import expert_matmul_pallas
            out = expert_matmul_pallas(x, w_gate, w_up, w_down, group_sizes,
                                       tm=tm, interpret=_interpret())
        else:
            out = ref.expert_matmul_ref(x, w_gate, w_up, w_down, group_sizes)
    return out


# ---------------------------------------------------------------------------
# Speculative-verification ops (the paper's server-side hot path)
# ---------------------------------------------------------------------------

def gather_softmax_prob(logits, token_ids):
    """p_target(token) for each row without materializing softmax(V)."""
    with _span("ops.gather_softmax_prob", logits):
        if _use_pallas():
            from .gather_softmax_prob import gather_softmax_prob_pallas
            out = gather_softmax_prob_pallas(logits, token_ids,
                                             interpret=_interpret())
        else:
            out = ref.gather_softmax_prob_ref(logits, token_ids)
    return out


def residual_sample(p, q, u):
    """Sample from normalize(max(p-q, 0)) via inverse CDF (paper eq. 5)."""
    with _span("ops.residual_sample", p):
        if _use_pallas():
            from .residual_sample import residual_sample_pallas
            out = residual_sample_pallas(p, q, u, interpret=_interpret())
        else:
            out = ref.residual_sample_ref(p, q, u)
    return out


def fused_verify_sample(target_logits, draft_tokens, draft_probs, q_idx,
                        q_val, u_accept, u_resid, draft_len=None):
    """Accept-test + prefix count + calibrated residual token, one dispatch.

    Fuses ``gather_softmax_prob`` over every drafted position, the accept
    test ``u < min(1, p_L/p_S)`` (masked to ``draft_len``), the prefix
    acceptance count, and ``residual_sample`` at the first rejected position
    with the sparse SLM row (q_idx, q_val) rebuilt tile-locally — the dense
    (B, V) residual distribution never touches HBM on the Pallas path.

    target_logits: (B, L+1, V); draft_tokens / draft_probs / u_accept:
    (B, L); q_idx / q_val: (B, L, Vhat); u_resid: (B,); draft_len: (B,)
    true lengths (defaults to L).  Uniforms are drawn by the caller so the
    rng stream matches the unfused path exactly.

    Returns ``(accept (B, L) bool, n_acc (B,) int32, calibrated (B,) int32)``.
    """
    B, L = draft_tokens.shape
    if draft_len is None:
        draft_len = jnp.full((B,), L, jnp.int32)
    with _span("ops.fused_verify_sample", target_logits):
        if _use_pallas():
            from .fused_verify_sample import fused_verify_sample_pallas
            out = fused_verify_sample_pallas(target_logits, draft_tokens,
                                             draft_probs, q_idx, q_val,
                                             u_accept, u_resid, draft_len,
                                             interpret=_interpret())
        else:
            out = ref.fused_verify_sample_ref(target_logits, draft_tokens,
                                              draft_probs, q_idx, q_val,
                                              u_accept, u_resid, draft_len)
    return out
