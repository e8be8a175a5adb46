"""The dense Qwen2 decoder (Qwen2.5 family, ``Qwen2ForCausalLM``) as the
benchmark builds, seeds and counts it.

Reads the published ``config.json`` keys of a model section: grouped-query
attention with q/k/v biases (``attention_bias``) and rotary embeddings, a
SwiGLU MLP of width ``intermediate_size``, RMSNorm, and tied embeddings.
The weights are laid out as the program's dense model holds them: embed,
ln_f, and layer-stacked blocks of ``ln_attn``, ``attn`` (wq, wk, wv, wo and
the biases), ``ln_mlp`` and ``mlp`` (w_gate, w_up, w_down).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import weights

ATTENTION_KERNEL = "paged_attention"
BIAS_SD = 0.5       # QKV biases: large enough to move every logit


def model_config(section: dict, name: str, dtype: str):
    from repro.configs.base import ModelConfig

    d, H = section["hidden_size"], section["num_attention_heads"]
    return ModelConfig(
        name=name, family="dense", num_layers=section["num_hidden_layers"],
        d_model=d, num_heads=H, num_kv_heads=section["num_key_value_heads"],
        head_dim=section.get("head_dim", d // H),
        d_ff=section["intermediate_size"], vocab_size=section["vocab_size"],
        activation="swiglu", norm="rmsnorm",
        qkv_bias=bool(section["attention_bias"]),
        rope_theta=float(section["rope_theta"]),
        tie_embeddings=bool(section["tie_word_embeddings"]),
        param_dtype=dtype, compute_dtype=dtype)


def shape(section: dict) -> tuple:
    """The static shape tuple of a model section:
    (V, d, layers, H, KV, Dh, F, qkv_bias)."""
    d, H = section["hidden_size"], section["num_attention_heads"]
    return (section["vocab_size"], d, section["num_hidden_layers"], H,
            section["num_key_value_heads"], section.get("head_dim", d // H),
            section["intermediate_size"], bool(section["attention_bias"]))


def blocks(key, shape: tuple, live, dt):
    """The layer-stacked blocks, one layer at a time (``lax.map``); the
    code dims' rows of every input projection and columns of every output
    projection are zero.  Call it inside a jitted function."""
    (V, d, n_layers, H, KV, Dh, F, qkv_bias) = shape
    normal = weights.normal

    def block(k):
        ks = jax.random.split(k, 12)
        rows_in = live[:, None]                   # zero the code dims' rows
        cols_out = live[None, :]                  # ... and output columns
        p = {
            "ln_attn": {"scale": weights.norm_scale(ks[7], live, dt)},
            "attn": {
                "wq": jnp.where(rows_in, normal(ks[0], (d, H * Dh),
                                                d ** -0.5, dt), 0),
                "wk": jnp.where(rows_in, normal(ks[1], (d, KV * Dh),
                                                d ** -0.5, dt), 0),
                "wv": jnp.where(rows_in, normal(ks[2], (d, KV * Dh),
                                                d ** -0.5, dt), 0),
                "wo": jnp.where(cols_out, normal(ks[3], (H * Dh, d),
                                                 (H * Dh) ** -0.5, dt), 0),
            },
            "ln_mlp": {"scale": weights.norm_scale(ks[8], live, dt)},
            "mlp": {
                "w_gate": jnp.where(rows_in, normal(ks[4], (d, F),
                                                    d ** -0.5, dt), 0),
                "w_up": jnp.where(rows_in, normal(ks[5], (d, F),
                                                  d ** -0.5, dt), 0),
                "w_down": jnp.where(cols_out, normal(ks[6], (F, d),
                                                     F ** -0.5, dt), 0),
            },
        }
        if qkv_bias:
            p["attn"]["bq"] = normal(ks[9], (H * Dh,), BIAS_SD, dt)
            p["attn"]["bk"] = normal(ks[10], (KV * Dh,), BIAS_SD, dt)
            p["attn"]["bv"] = normal(ks[11], (KV * Dh,), BIAS_SD, dt)
        return p

    return jax.lax.map(block, jax.random.split(key, n_layers))


@functools.partial(jax.jit, static_argnames=("shape", "acc", "dtype"))
def _make(key, *, shape: tuple, acc: tuple, dtype: str):
    V, d = shape[0], shape[1]
    dt = weights.DTYPES[dtype]
    k_embed, k_code, k_blocks = jax.random.split(key, 3)
    embed = weights.code_table(k_code, k_embed, V, d, acc, dt)
    k_blocks, k_lnf = jax.random.split(k_blocks)
    return {"embed": embed,
            "ln_f": {"scale": weights.final_scale(k_lnf, d, acc, dt)},
            "blocks": blocks(k_blocks, shape, weights.live_dims(d, acc), dt)}


def make_params(section: dict, acc: dict, key, dtype: str = "bfloat16"):
    """Weights of one model section from ``key``, with the acceptance
    construction, in one jitted call."""
    if not section["tie_word_embeddings"]:
        raise ValueError("archs/qwen2.py builds tied embeddings only")
    return _make(key, shape=shape(section),
                 acc=weights.acceptance_params(acc), dtype=dtype)


def _dims(m: dict) -> dict:
    d, H = m["hidden_size"], m["num_attention_heads"]
    return dict(d=d, H=H, KV=m["num_key_value_heads"],
                Dh=m.get("head_dim", d // H), F=m["intermediate_size"],
                V=m["vocab_size"], n=m["num_hidden_layers"])


def token_flops(m: dict) -> float:
    """Matrix FLOPs per token of one forward pass, attention excluded:
    q/k/v/o projections, the SwiGLU MLP, and the logits."""
    x = _dims(m)
    proj = x["d"] * x["Dh"] * (2 * x["H"] + 2 * x["KV"])
    mlp = 3 * x["d"] * x["F"]
    return 2.0 * (x["n"] * (proj + mlp) + x["d"] * x["V"])


def attention(m: dict, ctx0: int, T: int, kv_bytes: int = 2):
    """(flops, bytes) of causal attention for one row: T queries at
    positions ctx0 .. ctx0 + T - 1 over the cache, all layers.  Bytes read
    K and V of the ctx0 + T context tokens once, and q in / out once."""
    x = _dims(m)
    keys = T * ctx0 + T * (T + 1) // 2          # sum of context lengths
    flops = 4.0 * x["n"] * x["H"] * x["Dh"] * keys
    kv = 2 * x["KV"] * x["Dh"] * (ctx0 + T) * kv_bytes
    qo = 2 * x["H"] * x["Dh"] * T * 2
    return flops, float(x["n"] * (kv + qo))


def smoke(layers: int, d: int, heads: int, ff: int, vocab: int = 512) -> dict:
    """A smoke-size model section of this architecture, for CPU checks."""
    return {"arch": "qwen2", "hidden_size": d, "intermediate_size": ff,
            "num_attention_heads": heads, "num_hidden_layers": layers,
            "num_key_value_heads": 2, "vocab_size": vocab,
            "rms_norm_eps": 1e-06, "rope_theta": 1000000.0,
            "tie_word_embeddings": True, "attention_bias": True}
