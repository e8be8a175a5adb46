"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth used by (a) the kernel allclose
tests and (b) the CPU execution path of ``ops.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Flash attention (prefill, causal, GQA)
# ---------------------------------------------------------------------------

def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True, scale: float | None = None) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Skv, KV, D) -> (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    qg = q.reshape(B, Sq, KV, G, D)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    if causal:
        Skv = k.shape[1]
        mask = jnp.arange(Skv)[None, :] <= (jnp.arange(Sq)[:, None] + (Skv - Sq))
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, Sq, H, D)


# ---------------------------------------------------------------------------
# Decode attention (one query step against a KV cache with valid lengths)
# ---------------------------------------------------------------------------

def decode_attention_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         lengths: jax.Array, scale: float | None = None) -> jax.Array:
    """q: (B, H, D); caches: (B, S, KV, D); lengths: (B,) -> (B, H, D).

    Attends to cache positions [0, lengths_b).
    """
    B, H, D = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    S = k_cache.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    qg = q.reshape(B, KV, G, D)
    logits = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache).astype(jnp.float32) * scale
    valid = (jnp.arange(S)[None, :] < lengths[:, None])[:, None, None, :]
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, v_cache)
    return out.reshape(B, H, D)


def paged_attention_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        page_table: jax.Array, lengths: jax.Array,
                        scale: float | None = None) -> jax.Array:
    """Decode/window attention through a paged KV cache.

    q: (B, T, H, D) window queries (T=1 decode, T=L+1 verification).
    k_pool / v_pool: (P, ps, KV, D) physical page pools.
    page_table: (B, n_slots) int32, physical page per logical slot (-1 =
        unmapped: those positions are masked out).
    lengths: (B,) valid kv count for query row 0; query row t attends
        logical positions [0, lengths_b + t) — the window's own tokens are
        already in the pool (written before attention, matching
        ``forward_window``'s update-then-attend order).
    Returns (B, T, H, D).
    """
    B, T, H, D = q.shape
    P, ps, KV, _ = k_pool.shape
    n_slots = page_table.shape[1]
    S = n_slots * ps
    G = H // KV
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    safe = jnp.maximum(page_table, 0)
    k = k_pool[safe].reshape(B, S, KV, D)
    v = v_pool[safe].reshape(B, S, KV, D)
    qg = q.reshape(B, T, KV, G, D)
    logits = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32) * scale
    kpos = jnp.arange(S)
    valid = kpos[None, None, :] < (lengths[:, None]
                                   + jnp.arange(T)[None, :])[:, :, None]
    mapped = jnp.repeat(page_table >= 0, ps, axis=1)          # (B, S)
    valid = valid & mapped[:, None, :]
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(B, T, H, D)


def latent_attention_ref(q: jax.Array, pool: jax.Array, page_table: jax.Array,
                         lengths: jax.Array, *, dv: int,
                         scale: float) -> jax.Array:
    """Window attention of absorbed MLA queries through a paged latent pool.

    q: (B, T, H, C) queries in the latent space (the no-rope part projected
    through the key up-projection, then the roped part); pool: (P, ps, C)
    latent rows (normed c_kv, then the roped key); page_table / lengths as
    in ``paged_attention_ref``.  Scores are q . row; values are each row's
    first ``dv`` columns.  Returns (B, T, H, dv).
    """
    B, T, H, C = q.shape
    ps = pool.shape[1]
    n_slots = page_table.shape[1]
    S = n_slots * ps
    rows = pool[jnp.maximum(page_table, 0)].reshape(B, S, C)
    logits = jnp.einsum("bthc,bsc->bhts", q, rows).astype(jnp.float32) * scale
    kpos = jnp.arange(S)
    valid = kpos[None, None, :] < (lengths[:, None]
                                   + jnp.arange(T)[None, :])[:, :, None]
    valid = valid & jnp.repeat(page_table >= 0, ps, axis=1)[:, None, :]
    logits = jnp.where(valid[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(pool.dtype)
    return jnp.einsum("bhts,bsc->bthc", probs, rows[..., :dv])


def tree_attention_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                       lengths: jax.Array, win_mask: jax.Array,
                       scale: float | None = None) -> jax.Array:
    """Token-tree verification window over a contiguous KV cache.

    q: (B, T, H, D) — the T-token tree window (slot 0 is the pending token,
        slots 1.. are tree nodes in construction order).
    k_cache / v_cache: (B, S, KV, D); the window's K/V are already written
        at cache slots [lengths_b, lengths_b + T) (update-then-attend order,
        matching ``forward_window``).
    lengths: (B,) committed kv count — query rows attend every committed
        slot [0, lengths_b).
    win_mask: (B, T, T) bool — in-window attendance: query row t may attend
        window slot t' iff win_mask[b, t, t'] (ancestor-or-self of the token
        tree; a lower-triangular mask recovers the sequential causal window).
    Returns (B, T, H, D).
    """
    B, T, H, D = q.shape
    S = k_cache.shape[1]
    KV = k_cache.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    qg = q.reshape(B, T, KV, G, D)
    logits = jnp.einsum("btkgd,bskd->bkgts", qg,
                        k_cache).astype(jnp.float32) * scale
    kpos = jnp.arange(S)
    committed = kpos[None, None, :] < lengths[:, None, None]      # (B, 1, S)
    w = kpos[None, :] - lengths[:, None]                          # (B, S)
    in_win = (w >= 0) & (w < T)
    idx = jnp.broadcast_to(jnp.clip(w, 0, T - 1)[:, None, :], (B, T, S))
    allow = jnp.take_along_axis(win_mask, idx, axis=2)            # (B, T, S)
    valid = committed | (allow & in_win[:, None, :])
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v_cache)
    return out.reshape(B, T, H, D)


def paged_tree_attention_ref(q: jax.Array, k_pool: jax.Array,
                             v_pool: jax.Array, page_table: jax.Array,
                             lengths: jax.Array, win_mask: jax.Array,
                             scale: float | None = None) -> jax.Array:
    """``tree_attention_ref`` through a paged KV cache.

    Pools are (P, ps, KV, D); page_table is (B, n_slots) int32 (-1 =
    unmapped, masked out).  The window occupies logical positions
    [lengths_b, lengths_b + T), already written through the page table.
    """
    B = q.shape[0]
    ps = k_pool.shape[1]
    n_slots = page_table.shape[1]
    S = n_slots * ps
    KV, D = k_pool.shape[2], k_pool.shape[3]
    safe = jnp.maximum(page_table, 0)
    k = k_pool[safe].reshape(B, S, KV, D)
    v = v_pool[safe].reshape(B, S, KV, D)
    # the committed prefix and the window are always fully mapped (the
    # engine extends before writing), and the tree mask already excludes
    # every slot outside [0, lengths) u window — so the gathered view can
    # delegate straight to the contiguous oracle.
    return tree_attention_ref(q, k, v, lengths, win_mask, scale=scale)


def decode_attention_quantized_ref(q: jax.Array, k_cache: jax.Array,
                                   v_cache: jax.Array, k_scale: jax.Array,
                                   v_scale: jax.Array, lengths: jax.Array
                                   ) -> jax.Array:
    """Decode attention over an int8-quantized KV cache.

    k_cache/v_cache: (B, S, KV, D) int8; scales: (B, KV) per-head dequant
    factors.  Dequantize then run the exact fp path (the kernel fuses the
    dequant into the tile loads instead).
    """
    k = k_cache.astype(jnp.float32) * k_scale[:, None, :, None]
    v = v_cache.astype(jnp.float32) * v_scale[:, None, :, None]
    return decode_attention_ref(q, k, v, lengths)


def quantize_kv(k: jax.Array, v: jax.Array):
    """Per (batch, kv-head) symmetric int8 quantization of a KV cache."""
    def q_one(x):
        scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(1, 3)) / 127.0
        scale = jnp.maximum(scale, 1e-8)
        q = jnp.clip(jnp.round(x.astype(jnp.float32)
                               / scale[:, None, :, None]), -127, 127)
        return q.astype(jnp.int8), scale
    kq, ks = q_one(k)
    vq, vs = q_one(v)
    return kq, ks, vq, vs


# ---------------------------------------------------------------------------
# Fused softmax + gather (verification probabilities, paper eq. 3-4)
# ---------------------------------------------------------------------------

def gather_softmax_prob_ref(logits: jax.Array, token_ids: jax.Array) -> jax.Array:
    """logits: (N, V); token_ids: (N,) -> probability of each token (N,).

    p = softmax(logits)[token] computed without materializing softmax over V
    (reference does materialize; the kernel streams V tiles).
    """
    logits = logits.astype(jnp.float32)
    m = jnp.max(logits, axis=-1, keepdims=True)
    z = jnp.sum(jnp.exp(logits - m), axis=-1)
    picked = jnp.take_along_axis(logits, token_ids[:, None], axis=-1)[:, 0]
    return jnp.exp(picked - m[:, 0]) / z


# ---------------------------------------------------------------------------
# Residual-distribution sampling (paper eq. 5 calibrated token)
# ---------------------------------------------------------------------------

def residual_sample_ref(p: jax.Array, q: jax.Array, u: jax.Array) -> jax.Array:
    """Sample from normalize(max(p - q, 0)) by inverse CDF.

    p, q: (N, V) probability rows; u: (N,) uniforms in [0,1) -> tokens (N,).
    Falls back to argmax(p) when the residual is numerically all-zero
    (p == q), which matches rejection being impossible in exact arithmetic.
    """
    r = jnp.maximum(p.astype(jnp.float32) - q.astype(jnp.float32), 0.0)
    z = jnp.sum(r, axis=-1, keepdims=True)
    degenerate = z[:, 0] <= 0.0
    cdf = jnp.cumsum(r, axis=-1)
    target = u[:, None] * z
    token = jnp.sum((cdf <= target).astype(jnp.int32), axis=-1)
    token = jnp.minimum(token, p.shape[-1] - 1)
    return jnp.where(degenerate, jnp.argmax(p, axis=-1).astype(jnp.int32),
                     token.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Fused draft verification + calibrated sampling (paper eq. 4-5 in one op)
# ---------------------------------------------------------------------------

def _scatter_rows(out: jax.Array, idx: jax.Array, val: jax.Array) -> jax.Array:
    """Scatter-add (B, Vhat) sparse rows into dense (B, V) rows."""
    rows = jnp.arange(idx.shape[0])[:, None]
    return out.at[rows, idx].add(val)


def fused_verify_sample_ref(target_logits: jax.Array,   # (B, L+1, V)
                            draft_tokens: jax.Array,    # (B, L) int32
                            draft_probs: jax.Array,     # (B, L) p_S
                            q_idx: jax.Array,           # (B, L, Vhat) int32
                            q_val: jax.Array,           # (B, L, Vhat)
                            u_accept: jax.Array,        # (B, L) uniforms
                            u_resid: jax.Array,         # (B,) uniforms
                            draft_len: jax.Array,       # (B,) true L_k <= L
                            ):
    """One-dispatch oracle for accept-test + residual sampling.

    Composes ``gather_softmax_prob_ref`` (p_L of each drafted token), the
    accept test ``u < min(1, p_L/p_S)`` masked to ``draft_len``, the
    prefix-acceptance count, and ``residual_sample_ref`` at the first
    rejected position (sparse SLM distribution scattered dense) — exactly
    the math ``core.verification.verify_drafts`` used to run as separate
    dispatches, with the uniforms drawn by the caller so the rng stream is
    unchanged.

    Returns ``(accept (B, L) bool, n_acc (B,) int32, calibrated (B,) int32)``.
    The bonus token on full acceptance stays outside (it needs a categorical
    sample, not a residual one).
    """
    B, L = draft_tokens.shape
    V = target_logits.shape[-1]

    flat_logits = target_logits[:, :L].reshape(B * L, V)
    p_target = gather_softmax_prob_ref(
        flat_logits, draft_tokens.reshape(B * L)).reshape(B, L)

    ratio = p_target / jnp.maximum(draft_probs, 1e-30)
    accept = u_accept < jnp.minimum(ratio, 1.0)
    accept = accept & (jnp.arange(L)[None, :] < draft_len[:, None])
    prefix_ok = jnp.cumprod(accept.astype(jnp.int32), axis=-1)
    n_acc = jnp.sum(prefix_ok, axis=-1)

    sel = jnp.minimum(n_acc, L - 1)
    logits_rej = jnp.take_along_axis(
        target_logits, sel[:, None, None], axis=1)[:, 0]
    p_rej = jax.nn.softmax(logits_rej.astype(jnp.float32), axis=-1)
    idx_rej = jnp.take_along_axis(q_idx, sel[:, None, None], axis=1)[:, 0]
    val_rej = jnp.take_along_axis(q_val, sel[:, None, None], axis=1)[:, 0]
    q_rej = _scatter_rows(jnp.zeros((B, V), jnp.float32), idx_rej,
                          val_rej.astype(jnp.float32))
    calibrated = residual_sample_ref(p_rej, q_rej, u_resid)
    return accept, n_acc.astype(jnp.int32), calibrated


# ---------------------------------------------------------------------------
# Mamba-2 SSD (state-space duality) chunked scan
# ---------------------------------------------------------------------------

def _segsum(x: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{k=j+1..i} x[..., k] (i >= j),
    -inf above the diagonal."""
    T = x.shape[-1]
    xx = jnp.repeat(x[..., None], T, axis=-1)        # xx[..., k, j] = x_k
    mask = jnp.tril(jnp.ones((T, T), bool), k=-1)    # keep rows k > col j
    xx = jnp.where(mask, xx, 0.0)
    out = jnp.cumsum(xx, axis=-2)
    mask = jnp.tril(jnp.ones((T, T), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_scan_ref(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                 C: jax.Array, chunk: int = 64,
                 initial_state: jax.Array | None = None):
    """Chunked SSD forward (Mamba-2, arXiv:2405.21060 listing 1).

    x:  (b, s, h, p)   head inputs
    dt: (b, s, h)      positive step sizes (softplus already applied)
    A:  (h,)           negative decay rates
    B:  (b, s, g, n)   input projections (g groups, g | h)
    C:  (b, s, g, n)   output projections
    Returns (y (b, s, h, p), final_state (b, h, p, n)).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert s % chunk == 0, f"seq {s} not divisible by chunk {chunk}"
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2)  # (b, s, h, n)
    Ch = jnp.repeat(C, rep, axis=2)

    xw = (x * dt[..., None]).astype(jnp.float32)     # dt-weighted input
    Abar = (A[None, None, :] * dt).astype(jnp.float32)  # (b, s, h)

    c = s // chunk
    xw = xw.reshape(b, c, chunk, h, p)
    Bh = Bh.reshape(b, c, chunk, h, n).astype(jnp.float32)
    Ch = Ch.reshape(b, c, chunk, h, n).astype(jnp.float32)
    Ab = Abar.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)  # (b, h, c, l)
    A_cum = jnp.cumsum(Ab, axis=-1)                          # (b, h, c, l)

    # Intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(Ab))                                 # (b, h, c, l, l)
    Y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", Ch, Bh, L, xw)

    # Chunk end-states
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)          # (b, h, c, l)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", Bh, decay_states, xw)

    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), jnp.float32)
    states = jnp.concatenate([initial_state[:, None], states], axis=1)  # (b, c+1, h, p, n)

    # Inter-chunk recurrence
    chunk_decay = A_cum[..., -1]                             # (b, h, c)
    padded = jnp.pad(chunk_decay, ((0, 0), (0, 0), (1, 0)))
    decay_chunk = jnp.exp(_segsum(padded))                   # (b, h, c+1, c+1)
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states)
    prev_states, final_state = new_states[:, :-1], new_states[:, -1]

    # Inter-chunk contribution to outputs
    state_decay_out = jnp.exp(A_cum)                         # (b, h, c, l)
    Y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", Ch, prev_states, state_decay_out)

    y = (Y_diag + Y_off).reshape(b, s, h, p)
    return y.astype(x.dtype), final_state


def ssd_decode_ref(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                   C: jax.Array, state: jax.Array):
    """One-token SSD recurrence.

    x: (b, h, p); dt: (b, h); A: (h,); B, C: (b, g, n); state: (b, h, p, n).
    Returns (y (b, h, p), new_state).
    """
    b, h, p = x.shape
    g, n = B.shape[1], B.shape[2]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=1).astype(jnp.float32)      # (b, h, n)
    Ch = jnp.repeat(C, rep, axis=1).astype(jnp.float32)
    decay = jnp.exp(A[None, :] * dt)                          # (b, h)
    upd = (dt[..., None] * x.astype(jnp.float32))[..., None] * Bh[:, :, None, :]
    new_state = decay[..., None, None] * state + upd          # (b, h, p, n)
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Grouped expert matmul (dropless MoE)
# ---------------------------------------------------------------------------

def expert_matmul_ref(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                      w_down: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """SwiGLU experts over rows sorted by expert: rows
    ``[start_e, start_e + group_sizes[e])`` of x (M, d) use expert e's
    weights (E, d, F) / (E, F, d).  Returns (M, d) float32; rows past the
    last group are zero."""
    gate = jax.lax.ragged_dot(x, w_gate, group_sizes,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(x, w_up, group_sizes,
                            preferred_element_type=jnp.float32)
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    return jax.lax.ragged_dot(h, w_down, group_sizes,
                              preferred_element_type=jnp.float32)
