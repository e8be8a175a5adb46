"""moonshot-v1-16b-a3b — Moonlight-16B-A3B (moonshotai), the DeepSeek-V3
layout [hf:moonshotai/Moonlight-16B-A3B config.json].

27 layers at d 2048: the first dense (SwiGLU, width 11264), the other 26
with 64 routed experts of width 1408 (top-6, sigmoid scores with a
correction bias, normalised gates times 2.446) and 2 shared experts.  Every
layer uses multi-head latent attention without query compression: 16
heads, a 512-wide latent plus a 64-wide shared rope key, per-head no-rope
and value dims of 128.  Untied head, vocabulary 163840.

``from_config_json`` maps such a ``config.json`` onto ``ModelConfig``; the
benchmark's DeepSeek-V3 architecture (``bench/archs/deepseek_v3.py``)
builds its configuration through it too, so the two never disagree.
"""

from .base import ModelConfig, register

# the published config.json keys that shape the model
MOONLIGHT_16B_A3B = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 11264, "kv_lora_rank": 512,
    "max_position_embeddings": 8192, "model_type": "deepseek_v3",
    "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840,
}


def from_config_json(c: dict, name: str, dtype: str = "float32",
                     ) -> ModelConfig:
    """The ``ModelConfig`` of a DeepSeek-V3 ``config.json``.  A chip's share
    of an expert-parallel deployment gives ``n_routed_experts`` as the
    experts held here, with ``n_routed_experts_published`` (the router's
    width) and ``first_held_expert`` beside it."""
    supported = (c["q_lora_rank"] is None and c["n_group"] == 1
                 and c["topk_group"] == 1 and c["topk_method"] == "noaux_tc"
                 and c["scoring_func"] == "sigmoid" and c["norm_topk_prob"]
                 and c["moe_layer_freq"] == 1 and not c["attention_bias"]
                 and c["hidden_act"] == "silu")
    if not supported:
        raise NotImplementedError(
            "DeepSeek-V3 layouts without query compression, with one expert "
            "group, sigmoid noaux_tc routing and SiLU experts only")
    held = c["n_routed_experts"]
    routed = c.get("n_routed_experts_published", held)
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    return ModelConfig(
        name=name, family="moe", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=nope + rope,
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        activation="swiglu", norm="rmsnorm", norm_eps=c["rms_norm_eps"],
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), attention="mla",
        kv_lora_rank=c["kv_lora_rank"], qk_nope_head_dim=nope,
        qk_rope_head_dim=rope, v_head_dim=c["v_head_dim"],
        num_experts=routed, top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        num_shared_experts=c["n_shared_experts"],
        shared_d_ff=c["n_shared_experts"] * c["moe_intermediate_size"],
        first_k_dense=c["first_k_dense_replace"], router="sigmoid",
        routed_scale=float(c["routed_scaling_factor"]),
        experts_held=0 if held == routed else held,
        first_expert=c.get("first_held_expert", 0),
        param_dtype=dtype, compute_dtype=dtype)


MOONSHOT_16B = register(from_config_json(MOONLIGHT_16B_A3B,
                                         "moonshot-v1-16b-a3b"))
