"""Seeded weights for one model of a draft/target pair, with the acceptance
construction.

Random weights alone make speculation degenerate: a draft proposes from its
top-64 tokens and the target gives each about 1/151936, so every drafted
token is rejected.  The construction here makes the draft and the target
agree at the per-task rates of the paper's Table I without changing any
shape, dtype or amount of work; only values change.

    clusters   token y belongs to cluster y // m (m = ``cluster_size``);
               cluster k belongs to task k * n_tasks // n_clusters, so each
               task owns one contiguous range of the vocabulary.
    code       the last ``code_dims`` + 1 residual dims form a bypass that
               no layer reads or writes (those rows of wq/wk/wv/w_gate/w_up
               and columns of wo/w_down are zero).  Embedding row y holds
               ``code_norm * w_k(y)`` there (w_k a seeded unit vector per
               cluster) plus the constant ``code_norm`` in the last dim.
    ln_f       scale 1 + N(0, 0.1^2) on the other dims, ``code_gain`` on the
               code dims and ``-code_gain`` on the constant dim, so the code's share of a
               logit is ``gain * a^2 * (cos(w_k(x), w_k(y)) - 1) / rms(h)``:
               0 for every token of the current token's cluster and tens
               below for all others.  The next token therefore stays in the
               current token's cluster, and so in its task's range.
    spread     inside the cluster, the layers decide: the rest of
               embedding row y is N(0, sigma_task(y)^2 / d), so the logits
               of the m cluster tokens spread by about sigma_task(y), and
               that spread comes from the whole random stack.  Two
               independent models that spread by sigma over m tokens
               overlap by the ``task_alpha`` the config states; the
               ``task_sigma`` values are solved for that overlap.
    layers     every weight is seeded, so that the check sees each term
               of a block move the logits: the projections N(0, 1/fan_in),
               the QKV biases N(0, 0.5^2) and the RMSNorm scales
               1 + N(0, 0.1^2) (on the dims the layers use).

Everything is made on the device in one jitted call per model, in the
served dtype, layer by layer (``lax.map``) so no float32 copy of a whole
stack is ever live.  The reference regenerates the same values by calling
the same function with the same key.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
BIAS_SD = 0.5       # QKV biases: large enough to move every logit
NORM_SD = 0.1       # RMSNorm scales: 1 + N(0, NORM_SD^2) on the live dims


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def cluster_task(n_clusters: int, n_tasks: int) -> np.ndarray:
    """Task index of every cluster: contiguous ranges of equal size."""
    return (np.arange(n_clusters) * n_tasks) // n_clusters


@functools.partial(jax.jit, static_argnames=("shape", "acc", "dtype"))
def _make(key, *, shape: tuple, acc: tuple, dtype: str):
    (V, d, n_layers, H, KV, Dh, F, qkv_bias) = shape
    (m, r, a, gain, sigmas) = acc
    dt = DTYPES[dtype]
    n_code = r + 1
    live = jnp.arange(d) < d - n_code            # dims the layers use
    k_embed, k_code, k_blocks = jax.random.split(key, 3)

    # embedding, in row chunks so no float32 (V, d) copy is live
    n_clusters = V // m
    task_of_cluster = jnp.asarray(cluster_task(n_clusters, len(sigmas)))
    sig = jnp.asarray(sigmas, jnp.float32)[task_of_cluster]      # (C,)
    codes = jax.random.normal(k_code, (n_clusters, r), jnp.float32)
    codes = codes / jnp.linalg.norm(codes, axis=-1, keepdims=True)
    n_chunks = 16 if V % (16 * m) == 0 else 1
    rows = V // n_chunks

    def embed_chunk(i):
        y = i * rows + jnp.arange(rows)
        c = y // m
        rnd = jax.random.normal(jax.random.fold_in(k_embed, i),
                                (rows, d - n_code), jnp.float32)
        rnd = rnd * (sig[c] / np.sqrt(d))[:, None]
        code = a * codes[c]
        const = jnp.full((rows, 1), a, jnp.float32)
        return jnp.concatenate([rnd, code, const], axis=1).astype(dt)

    embed = jax.lax.map(embed_chunk, jnp.arange(n_chunks)).reshape(V, d)

    def scale(k):
        return jnp.where(live, 1.0 + _normal(k, (d,), NORM_SD, jnp.float32),
                         1.0).astype(dt)

    def block(k):
        ks = jax.random.split(k, 12)
        rows_in = live[:, None]                   # zero the code dims' rows
        cols_out = live[None, :]                  # ... and output columns
        p = {
            "ln_attn": {"scale": scale(ks[7])},
            "attn": {
                "wq": jnp.where(rows_in, _normal(ks[0], (d, H * Dh),
                                                 d ** -0.5, dt), 0),
                "wk": jnp.where(rows_in, _normal(ks[1], (d, KV * Dh),
                                                 d ** -0.5, dt), 0),
                "wv": jnp.where(rows_in, _normal(ks[2], (d, KV * Dh),
                                                 d ** -0.5, dt), 0),
                "wo": jnp.where(cols_out, _normal(ks[3], (H * Dh, d),
                                                  (H * Dh) ** -0.5, dt), 0),
            },
            "ln_mlp": {"scale": scale(ks[8])},
            "mlp": {
                "w_gate": jnp.where(rows_in, _normal(ks[4], (d, F),
                                                     d ** -0.5, dt), 0),
                "w_up": jnp.where(rows_in, _normal(ks[5], (d, F),
                                                   d ** -0.5, dt), 0),
                "w_down": jnp.where(cols_out, _normal(ks[6], (F, d),
                                                      F ** -0.5, dt), 0),
            },
        }
        if qkv_bias:
            p["attn"]["bq"] = _normal(ks[9], (H * Dh,), BIAS_SD, dt)
            p["attn"]["bk"] = _normal(ks[10], (KV * Dh,), BIAS_SD, dt)
            p["attn"]["bv"] = _normal(ks[11], (KV * Dh,), BIAS_SD, dt)
        return p

    k_blocks, k_lnf = jax.random.split(k_blocks)
    blocks = jax.lax.map(block, jax.random.split(k_blocks, n_layers))
    ln_f = jnp.concatenate([1.0 + _normal(k_lnf, (d - n_code,), NORM_SD,
                                          jnp.float32),
                            jnp.full((r,), gain, jnp.float32),
                            jnp.full((1,), -gain, jnp.float32)]).astype(dt)
    return {"embed": embed, "ln_f": {"scale": ln_f}, "blocks": blocks}


def model_shape(m: dict) -> tuple:
    """The static shape tuple of one model section of a config file."""
    d, H = m["hidden_size"], m["num_attention_heads"]
    return (m["vocab_size"], d, m["num_hidden_layers"], H,
            m["num_key_value_heads"], m.get("head_dim", d // H),
            m["intermediate_size"], bool(m["attention_bias"]))


def acceptance_params(acc: dict) -> tuple:
    return (int(acc["cluster_size"]), int(acc["code_dims"]),
            float(acc["code_norm"]), float(acc["code_gain"]),
            tuple(float(s) for s in acc["task_sigma"]))


def make_params(model: dict, acc: dict, key, dtype: str = "bfloat16"):
    """Weights of one model (a ``target``/``draft`` section of a config
    file) from ``key``, in the tree layout of a tied-embedding dense
    decoder: embed, ln_f, and layer-stacked blocks."""
    if not model["tie_word_embeddings"]:
        raise ValueError("the acceptance construction needs tied embeddings")
    return _make(key, shape=model_shape(model), acc=acceptance_params(acc),
                 dtype=dtype)


def pair_keys(seed: int):
    """(target key, draft key) for a run seed; any integer below 2**62."""
    base = jax.random.PRNGKey(seed % (2 ** 31))
    base = jax.random.fold_in(base, seed // (2 ** 31))
    kt, kd = jax.random.split(base)
    return kt, kd

