"""The DeepSeek-V3 decoder (``model_type`` ``deepseek_v3``, as Moonlight-16B-
A3B publishes it) as the benchmark builds, seeds and counts it.

Reads the published ``config.json`` keys of a model section: multi-head
latent attention without query compression (``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``), leading dense
SwiGLU layers (``first_k_dense_replace``, ``intermediate_size``), then
layers of sigmoid-routed experts (``n_routed_experts`` held here of
``n_routed_experts_published``, top ``num_experts_per_tok``, width
``moe_intermediate_size``) with ``n_shared_experts`` shared ones, RMSNorm,
and an untied head.  The program's ``ModelConfig`` comes from the
program's own mapping of those keys (``from_config_json``).  The weights
are laid out as the program holds them: embed, unembed, ln_f, the leading
``dense_blocks`` and the layer-stacked ``blocks``, each of ``ln_attn``,
``attn`` (wq, wkv_a, kv_norm, wkv_b, wo), ``ln_mlp`` and ``mlp`` (w_gate,
w_up, w_down) or ``moe`` (router, bias, the held experts' w_gate, w_up,
w_down, and ``shared``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import weights

ATTENTION_KERNEL = "latent_attention"
BIAS_SD = 0.1       # e_score_correction_bias: N(0, BIAS_SD^2), float32


def model_config(section: dict, name: str, dtype: str):
    from repro.configs.moonshot_v1_16b_a3b import from_config_json

    return from_config_json(section, name, dtype)


def shape(section: dict) -> tuple:
    """The static shape tuple of a model section: (V, d, n_dense, n_moe, H,
    nope, rope, v, rank, F_dense, E_router, E_held, F_expert, F_shared)."""
    s = section
    n_dense = s["first_k_dense_replace"]
    return (s["vocab_size"], s["hidden_size"], n_dense,
            s["num_hidden_layers"] - n_dense, s["num_attention_heads"],
            s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"],
            s["kv_lora_rank"], s["intermediate_size"],
            s.get("n_routed_experts_published", s["n_routed_experts"]),
            s["n_routed_experts"], s["moe_intermediate_size"],
            s["n_shared_experts"] * s["moe_intermediate_size"])


def _swiglu(ks, d, F, live, dt, lead=()):
    """w_gate, w_up (lead + (d, F)) reading the live dims; w_down
    (lead + (F, d)) writing them."""
    normal = weights.normal
    rows_in = live[:, None]
    cols_out = live[None, :]
    return {
        "w_gate": jnp.where(rows_in, normal(ks[0], lead + (d, F),
                                            d ** -0.5, dt), 0),
        "w_up": jnp.where(rows_in, normal(ks[1], lead + (d, F),
                                          d ** -0.5, dt), 0),
        "w_down": jnp.where(cols_out, normal(ks[2], lead + (F, d),
                                             F ** -0.5, dt), 0),
    }


def blocks(key, shape: tuple, live, dt, moe: bool):
    """One layer stack, one layer at a time (``lax.map``): the code dims'
    rows of every input projection (wq, wkv_a, the router, the experts'
    and the MLPs' gate and up) and columns of every output projection (wo,
    w_down) are zero.  Call it inside a jitted function."""
    (V, d, n_dense, n_moe, H, nope, rope, v, rank, F, E, held, Fe,
     Fs) = shape
    normal = weights.normal
    rows_in = live[:, None]
    cols_out = live[None, :]

    def block(k):
        ks = jax.random.split(k, 16)
        p = {
            "ln_attn": {"scale": weights.norm_scale(ks[0], live, dt)},
            "attn": {
                "wq": jnp.where(rows_in, normal(ks[1], (d, H * (nope + rope)),
                                                d ** -0.5, dt), 0),
                "wkv_a": jnp.where(rows_in, normal(ks[2], (d, rank + rope),
                                                   d ** -0.5, dt), 0),
                "kv_norm": {"scale": weights.norm_scale(
                    ks[3], jnp.ones((rank,), bool), dt)},
                "wkv_b": normal(ks[4], (rank, H * (nope + v)), rank ** -0.5,
                                dt),
                "wo": jnp.where(cols_out, normal(ks[5], (H * v, d),
                                                 (H * v) ** -0.5, dt), 0),
            },
            "ln_mlp": {"scale": weights.norm_scale(ks[6], live, dt)},
        }
        if not moe:
            p["mlp"] = _swiglu(ks[7:10], d, F, live, dt)
            return p
        p["moe"] = dict(
            router=jnp.where(rows_in, normal(ks[10], (d, E), d ** -0.5,
                                             jnp.float32), 0),
            bias=normal(ks[11], (E,), BIAS_SD, jnp.float32),
            shared=_swiglu(ks[12:15], d, Fs, live, dt),
            **_swiglu(ks[7:10], d, Fe, live, dt, lead=(held,)))
        return p

    n = n_moe if moe else n_dense
    return jax.lax.map(block, jax.random.split(key, n))


@functools.partial(jax.jit, static_argnames=("shape", "acc", "dtype"))
def _make(key, *, shape: tuple, acc: tuple, dtype: str):
    V, d = shape[0], shape[1]
    dt = weights.DTYPES[dtype]
    k_embed, k_code, k_blocks, k_head, k_lnf, k_dense = jax.random.split(
        key, 6)
    live = weights.live_dims(d, acc)
    out = {"embed": weights.code_table(k_code, k_embed, V, d, acc, dt),
           "unembed": weights.code_table(k_code, k_head, V, d, acc, dt).T,
           "ln_f": {"scale": weights.final_scale(k_lnf, d, acc, dt)},
           "blocks": blocks(k_blocks, shape, live, dt, moe=True)}
    if shape[2]:
        out["dense_blocks"] = blocks(k_dense, shape, live, dt, moe=False)
    return out


def make_params(section: dict, acc: dict, key, dtype: str = "bfloat16"):
    """Weights of one model section from ``key``, with the acceptance
    construction (an untied head carries the embedding's codes), in one
    jitted call."""
    if section["tie_word_embeddings"]:
        raise ValueError("archs/deepseek_v3.py builds an untied head only")
    return _make(key, shape=shape(section),
                 acc=weights.acceptance_params(acc), dtype=dtype)


def experts_per_token(m: dict) -> float:
    """Expected routed experts a token runs here: top-k times the share
    of the router's experts held here."""
    routed = m.get("n_routed_experts_published", m["n_routed_experts"])
    return m["num_experts_per_tok"] * m["n_routed_experts"] / routed


def token_flops(m: dict) -> float:
    """Matrix FLOPs per token of one forward pass, attention excluded: the
    MLA projections (q, latent and rope key, the per-token key and value
    up-projection, o), the dense layers' SwiGLU, the MoE layers' router,
    expected routed experts (``experts_per_token``) and shared expert, and
    the logits."""
    (V, d, n_dense, n_moe, H, nope, rope, v, rank, F, E, held, Fe,
     Fs) = shape(m)
    attn = (d * H * (nope + rope) + d * (rank + rope) + rank * H * (nope + v)
            + H * v * d)
    moe = d * E + 3 * d * Fe * experts_per_token(m) + 3 * d * Fs
    return 2.0 * ((n_dense + n_moe) * attn + n_dense * 3 * d * F
                  + n_moe * moe + d * V)


def attention(m: dict, ctx0: int, T: int, kv_bytes: int = 2):
    """(flops, bytes) of one row's attention over T queries at positions
    ctx0 .. ctx0 + T - 1, all layers.  A window against the cache (ctx0 >
    0) runs the absorbed form: per head and context token, a dot product
    over the latent row (rank + rope) and a weighted sum of its latent part
    (rank); bytes read the latent rows of the ctx0 + T context tokens once,
    and q in / out once.  A prefill (ctx0 = 0, from an empty cache) runs
    the expanded form: (nope + rope) and v dims per head and key."""
    (V, d, n_dense, n_moe, H, nope, rope, v, rank, F, E, held, Fe,
     Fs) = shape(m)
    n = n_dense + n_moe
    keys = T * ctx0 + T * (T + 1) // 2          # sum of context lengths
    C = rank + rope
    if ctx0 == 0:
        flops = 2.0 * n * H * (nope + rope + v) * keys
        qo = H * (nope + rope + v) * T * 2
    else:
        flops = 2.0 * n * H * (C + rank) * keys
        qo = H * (C + rank) * T * 2
    return flops, float(n * (C * (ctx0 + T) * kv_bytes + qo))


def expert_bytes(m: dict) -> int:
    """Bytes of one routed expert's three matrices in the served dtype
    (bf16)."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"] * 2


def smoke(layers: int = 3, d: int = 128, heads: int = 4, vocab: int = 512,
          held: int = 4, routed: int = 16) -> dict:
    """A smoke-size model section of this architecture, for CPU checks:
    MLA with a rope part, one leading dense layer, ``held`` experts of a
    ``routed``-wide router (top 4), two shared experts, untied head."""
    return {"arch": "deepseek_v3", "attention_bias": False,
            "first_k_dense_replace": 1, "hidden_act": "silu",
            "hidden_size": d, "intermediate_size": 2 * d,
            "kv_lora_rank": 32, "max_position_embeddings": 8192,
            "model_type": "deepseek_v3", "moe_intermediate_size": 32,
            "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": held,
            "n_routed_experts_published": routed, "first_held_expert": 0,
            "n_shared_experts": 2, "norm_topk_prob": True,
            "num_attention_heads": heads, "num_experts_per_tok": 4,
            "num_hidden_layers": layers, "num_key_value_heads": heads,
            "q_lora_rank": None, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "rms_norm_eps": 1e-05,
            "rope_theta": 50000, "routed_scaling_factor": 2.446,
            "scoring_func": "sigmoid", "tie_word_embeddings": False,
            "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 16,
            "vocab_size": vocab}
