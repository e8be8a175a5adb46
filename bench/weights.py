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
               no layer reads or writes (``live_dims``: the architecture
               zeroes those rows of every weight that reads the residual
               stream and those columns of every one that writes it, such
               as wq/wk/wv/w_gate/w_up and wo/w_down).  Embedding row y holds
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
               of a block move the logits: for Qwen2 the projections
               N(0, 1/fan_in), the QKV biases N(0, 0.5^2) and the RMSNorm
               scales 1 + N(0, 0.1^2) (on the dims the layers use).

This module holds what no architecture changes: the cluster/task map, the
code-carrying token table (``code_table``, also for an untied output head,
which then carries the embedding's codes), the norm scales and the final
norm's code gain.  Each architecture module (``bench/archs/<arch>.py``)
builds its own layers around them and makes everything on the device in
one jitted call per model, in the served dtype, layer by layer
(``lax.map``) so no float32 copy of a whole stack is ever live.  The
reference regenerates the same values by calling the same function with
the same key.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
NORM_SD = 0.1       # RMSNorm scales: 1 + N(0, NORM_SD^2) on the live dims


def normal(key, shape, scale, dtype):
    """N(0, scale^2) of ``shape``, drawn in float32, in ``dtype``."""
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def cluster_task(n_clusters: int, n_tasks: int) -> np.ndarray:
    """Task index of every cluster: contiguous ranges of equal size."""
    return (np.arange(n_clusters) * n_tasks) // n_clusters


def live_dims(d: int, acc: tuple):
    """(d,) mask of the residual dims the layers use: all but the code's
    ``code_dims`` + 1 bypass dims at the end."""
    return jnp.arange(d) < d - (acc[1] + 1)


def norm_scale(key, live, dtype):
    """An RMSNorm scale: 1 + N(0, NORM_SD^2) on the live dims, 1 on the
    code dims."""
    d = live.shape[0]
    return jnp.where(live, 1.0 + normal(key, (d,), NORM_SD, jnp.float32),
                     1.0).astype(dtype)


def code_table(k_code, k_rows, V: int, d: int, acc: tuple, dtype):
    """A (V, d) table of token rows that carries the cluster codes: the
    random spread from ``k_rows`` on the live dims, ``code_norm * w_k(y)``
    and the constant ``code_norm`` on the code dims, with the cluster
    codes w_k drawn from ``k_code``.  Two tables from one ``k_code`` (an
    embedding and an untied output head) carry the same codes.  Built in
    row chunks, so no float32 (V, d) copy is live; call it inside a jitted
    function."""
    (m, r, a, _, sigmas) = acc
    n_code = r + 1
    n_clusters = V // m
    task_of_cluster = jnp.asarray(cluster_task(n_clusters, len(sigmas)))
    sig = jnp.asarray(sigmas, jnp.float32)[task_of_cluster]      # (C,)
    codes = jax.random.normal(k_code, (n_clusters, r), jnp.float32)
    codes = codes / jnp.linalg.norm(codes, axis=-1, keepdims=True)
    n_chunks = 16 if V % (16 * m) == 0 else 1
    rows = V // n_chunks

    def chunk(i):
        y = i * rows + jnp.arange(rows)
        c = y // m
        rnd = jax.random.normal(jax.random.fold_in(k_rows, i),
                                (rows, d - n_code), jnp.float32)
        rnd = rnd * (sig[c] / np.sqrt(d))[:, None]
        code = a * codes[c]
        const = jnp.full((rows, 1), a, jnp.float32)
        return jnp.concatenate([rnd, code, const], axis=1).astype(dtype)

    return jax.lax.map(chunk, jnp.arange(n_chunks)).reshape(V, d)


def final_scale(key, d: int, acc: tuple, dtype):
    """The final norm's scale: 1 + N(0, NORM_SD^2) on the live dims,
    ``code_gain`` on the code dims and ``-code_gain`` on the constant dim."""
    r, gain = acc[1], acc[3]
    return jnp.concatenate([1.0 + normal(key, (d - r - 1,), NORM_SD,
                                          jnp.float32),
                            jnp.full((r,), gain, jnp.float32),
                            jnp.full((1,), -gain, jnp.float32)]).astype(dtype)


def acceptance_params(acc: dict) -> tuple:
    """The acceptance section as the static tuple the helpers above take:
    (cluster_size, code_dims, code_norm, code_gain, task_sigma)."""
    return (int(acc["cluster_size"]), int(acc["code_dims"]),
            float(acc["code_norm"]), float(acc["code_gain"]),
            tuple(float(s) for s in acc["task_sigma"]))


def pair_keys(seed: int):
    """(target key, draft key) for a run seed; any integer below 2**62."""
    base = jax.random.PRNGKey(seed % (2 ** 31))
    base = jax.random.fold_in(base, seed // (2 ** 31))
    kt, kd = jax.random.split(base)
    return kt, kd

