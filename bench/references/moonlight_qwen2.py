"""Plain float32 reference of a DeepSeek-V3 target (Moonlight-16B-A3B) and a
Qwen2 draft, told apart by each section's ``"arch"``.

The draft goes to ``references.qwen2``.  The target follows the published
DeepSeek-V3 modeling code (``modeling_deepseek.py``) for a config without
query compression and with one expert group:

  attention  q = h Wq, split per head into 128 no-rope and 64 rope dims;
             [c_kv, k_pe] = h W_kv_a; c_kv = RMSNorm(c_kv) (eps 1e-6, the
             modeling code's default for ``kv_a_layernorm``); [k_nope, v] =
             c_kv W_kv_b per head; the rope dims of q and k_pe are
             de-interleaved (even dims, then odd) and rotated half against
             half (theta from the config, no scaling); scores over
             [q_nope, q_pe] . [k_nope, k_pe] times 1/sqrt(nope + rope),
             causal softmax, values v, output projection;
  dense      the leading ``first_k_dense_replace`` layers: SwiGLU;
  experts    router logits h W_router in float32, sigmoid scores, top-k of
             score + ``e_score_correction_bias``, gates the chosen scores
             normalised to sum 1 and times ``routed_scaling_factor``; the
             routed experts' SwiGLU outputs weighted by their gates, plus
             the shared experts (one SwiGLU of ``n_shared_experts`` times
             the expert width);
  norms      RMSNorm with ``rms_norm_eps`` (1e-5) around every block and
             at the end; logits against the untied head.

Departures, both the configuration's cut: only the experts held here
(``n_routed_experts`` of ``n_routed_experts_published``) add to the routed
sum, as in the program, and the vocabulary is the draft's slice.

No cache, no kernels, no absorbed form: a full causal forward over whole
sequences, one layer at a time so that only one layer's weights are ever
upcast to float32.  Matrix products run at ``highest`` precision; ``fp8``
rounds both operands of every product to float8_e4m3 (the control).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from references import qwen2

EPS = 1e-5          # Moonlight's rms_norm_eps (blocks and the final norm)
KV_EPS = 1e-6       # kv_a_layernorm: the modeling code's default
matmul, _f32 = qwen2.matmul, qwen2._f32


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta: float):
    """The modeling code's RoPE: x (N, T, heads, D) at positions 0..T-1,
    de-interleaved (view (D/2, 2), transposed), then rotate-half with the
    cos / sin of cat(freqs, freqs)."""
    N, T, n, D = x.shape
    inv = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float64) / D)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    emb = np.concatenate([ang, ang], axis=-1)
    cos = jnp.asarray(np.cos(emb), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(emb), jnp.float32)[None, :, None, :]
    x = x.reshape(N, T, n, D // 2, 2).swapaxes(-1, -2).reshape(N, T, n, D)
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + rot * sin


def swiglu(h, p, precision):
    g = matmul(h, p["w_gate"], precision)
    u = matmul(h, p["w_up"], precision)
    return matmul(jax.nn.silu(g) * u, p["w_down"], precision)


def route(h, p, top_k: int, scale: float, precision: str):
    """(expert ids, gates) of every token: the float32 sigmoid router."""
    scores = jax.nn.sigmoid(matmul(h, p["router"], precision))
    _, idx = jax.lax.top_k(scores + p["bias"], top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale


@functools.partial(jax.jit, static_argnames=(
    "H", "nope", "theta", "moe", "top_k", "scale", "first", "precision"))
def layer(x, p, *, H: int, nope: int, theta: float, moe: bool, top_k: int,
          scale: float, first: int, precision: str):
    """One decoder layer over x (N, T, d) float32 with causal attention."""
    p = _f32(p)
    N, T, d = x.shape
    a = p["attn"]
    rank = a["wkv_b"].shape[0]
    h = rmsnorm(x, p["ln_attn"]["scale"], EPS)
    q = matmul(h, a["wq"], precision).reshape(N, T, H, -1)
    kv = matmul(h, a["wkv_a"], precision)
    c = rmsnorm(kv[..., :rank], a["kv_norm"]["scale"], KV_EPS)
    kvb = matmul(c, a["wkv_b"], precision).reshape(N, T, H, -1)
    k_pe = rope(kv[:, :, None, rank:], theta)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (N, T, H, k_pe.shape[-1]))],
                        -1)
    v = kvb[..., nope:]
    s = jnp.einsum("nthd,nshd->nhts", q, k,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("nhts,nshd->nthd", jax.nn.softmax(s, axis=-1), v,
                   precision=jax.lax.Precision.HIGHEST).reshape(N, T, -1)
    x = x + matmul(o, a["wo"], precision)
    h = rmsnorm(x, p["ln_mlp"]["scale"], EPS)
    if not moe:
        return x + swiglu(h, p["mlp"], precision)
    return x + experts(h, p["moe"], top_k=top_k, scale=scale, first=first,
                       precision=precision)


def experts(h, m, *, top_k: int, scale: float, first: int, precision: str):
    """The expert sublayer on normed h (..., d) float32: the held experts'
    SwiGLU outputs weighted by their gates (zero for tokens not routed to
    them), then the shared expert."""
    idx, w = route(h, m, top_k, scale, precision)

    def expert(y, e):
        pe = jax.tree.map(lambda t: t[e], {k: m[k] for k in
                                           ("w_gate", "w_up", "w_down")})
        gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        return y + gate[..., None] * swiglu(h, pe, precision), None

    held = m["w_gate"].shape[0]
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(held))
    return y + swiglu(h, m["shared"], precision)


def hidden_states(params, model: dict, tokens, precision: str = "f32"):
    """Final hidden states (N, T, d) of token rows (N, T), layer by layer."""
    if model["arch"] == "qwen2":
        return qwen2.hidden_states(params, model, tokens, precision)
    kw = dict(H=model["num_attention_heads"], nope=model["qk_nope_head_dim"],
              theta=float(model["rope_theta"]),
              top_k=model["num_experts_per_tok"],
              scale=float(model["routed_scaling_factor"]),
              first=model.get("first_held_expert", 0), precision=precision)
    x = qwen2.embed(jnp.asarray(tokens, jnp.int32), params["embed"])
    for name, moe in (("dense_blocks", False), ("blocks", True)):
        n = jax.tree.leaves(params[name])[0].shape[0] if name in params else 0
        for i in range(n):
            p = jax.tree.map(lambda a: a[i], params[name])
            x = layer(x, p, moe=moe, **kw)
    return x


@functools.partial(jax.jit, static_argnames=("precision",))
def _log_probs(x, ln_f, head, *, precision: str):
    h = rmsnorm(x, ln_f.astype(jnp.float32), EPS)
    logits = matmul(h, head.astype(jnp.float32), precision)
    return jax.nn.log_softmax(logits, axis=-1)


def sequence_log_probs(params, x_row, precision: str = "f32"):
    """(T, V) log-softmax of the next token at every position of one row;
    the target is the model with an untied head (``unembed``)."""
    if "unembed" not in params:
        return qwen2.sequence_log_probs(params, x_row, precision)
    return _log_probs(x_row, params["ln_f"]["scale"], params["unembed"],
                      precision=precision)
