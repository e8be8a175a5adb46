"""Plain reference of the Qwen2 decoder (Qwen2.5 family) in float32.

Follows the published Qwen2 architecture (transformers ``Qwen2Model``):
token embedding; per layer RMSNorm (eps 1e-6) -> grouped-query attention
with q/k/v biases and rotary embeddings (theta from the config, rotate-half
convention) -> residual; RMSNorm -> SwiGLU MLP -> residual; final RMSNorm;
logits against the tied embedding.  No cache, no kernels, no batching
tricks: a full causal forward over whole sequences, one layer at a time so
that only one layer's weights are ever upcast to float32.  Matrix products
run at ``highest`` precision, since on a TPU a float32 product otherwise
runs in bfloat16 passes.

``matmul`` is the one place a lower precision can be put in for the
control: ``fp8`` rounds both operands of every product to float8_e4m3 with
a per-tensor scale, the step a later change might take for speed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
F8_MAX = 448.0


def _round_fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def matmul(x, w, precision: str):
    if precision == "fp8":
        x, w = _round_fp8(x), _round_fp8(w)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, scale):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * scale


def rope(x, theta: float):
    """x: (N, T, heads, Dh); positions 0..T-1; rotate-half pairs."""
    T, Dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, Dh, 2, dtype=np.float64) / Dh)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :Dh // 2], x[..., Dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("H", "KV", "theta", "precision"))
def layer(x, p, *, H: int, KV: int, theta: float, precision: str):
    """One decoder layer over x (N, T, d) float32 with causal attention."""
    p = _f32(p)
    N, T, d = x.shape
    Dh = p["attn"]["wq"].shape[1] // H
    h = rmsnorm(x, p["ln_attn"]["scale"])
    q = matmul(h, p["attn"]["wq"], precision) + p["attn"]["bq"]
    k = matmul(h, p["attn"]["wk"], precision) + p["attn"]["bk"]
    v = matmul(h, p["attn"]["wv"], precision) + p["attn"]["bv"]
    q = rope(q.reshape(N, T, H, Dh), theta)
    k = rope(k.reshape(N, T, KV, Dh), theta)
    v = v.reshape(N, T, KV, Dh)
    G = H // KV
    q = q.reshape(N, T, KV, G, Dh)
    s = jnp.einsum("ntkgd,nskd->nkgts", q, k,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(Dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nkgts,nskd->ntkgd", a, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(N, T, H * Dh)
    x = x + matmul(o, p["attn"]["wo"], precision)
    h = rmsnorm(x, p["ln_mlp"]["scale"])
    g = matmul(h, p["mlp"]["w_gate"], precision)
    u = matmul(h, p["mlp"]["w_up"], precision)
    return x + matmul(jax.nn.silu(g) * u, p["mlp"]["w_down"], precision)


@jax.jit
def embed(tokens, table):
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("precision",))
def log_probs(x, ln_f, table, *, precision: str):
    """(T, d) final hidden states of one sequence -> (T, V) log-softmax."""
    h = rmsnorm(x, ln_f.astype(jnp.float32))
    logits = matmul(h, table.astype(jnp.float32).T, precision)
    return jax.nn.log_softmax(logits, axis=-1)


def hidden_states(params, model: dict, tokens, precision: str = "f32"):
    """Final hidden states (N, T, d) of token rows (N, T), layer by layer."""
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    theta = float(model["rope_theta"])
    x = embed(jnp.asarray(tokens, jnp.int32), params["embed"])
    for i in range(model["num_hidden_layers"]):
        p = jax.tree.map(lambda a: a[i], params["blocks"])
        x = layer(x, p, H=H, KV=KV, theta=theta, precision=precision)
    return x


def sequence_log_probs(params, x_row, precision: str = "f32"):
    """(T, V) log-softmax of the next token at every position of one row."""
    return log_probs(x_row, params["ln_f"]["scale"], params["embed"],
                     precision=precision)
