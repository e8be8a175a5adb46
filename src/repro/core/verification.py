"""Server-side speculative verification (paper Sec. II-A2, eq. 4-5).

Implements exact speculative sampling [Leviathan et al. 2023]: each drafted
token is accepted with probability min(1, p_L/p_S); the first rejected
position is replaced by a sample from the calibrated residual distribution
normalize(max(p_L - p_S, 0)); full acceptance earns one bonus token from the
LLM distribution.  The composition is distributed exactly as LLM sampling —
property-tested in tests/test_verification.py.

Supports both dense SLM distributions (co-located engine path) and the
paper's uplink-compressed sparse form (top-|V^hat| values + indices, Sec.
II-B): the device samples from the truncated+renormalized SLM distribution
and uploads exactly that distribution, so verification remains exact.

``verify_tree`` (multi-draft token trees) intentionally implements the
``multidraft`` scheme's MAX-OF-J acceptance law instead of exact
multi-draft speculative sampling — see its docstring for the
distributional tradeoff at J > 1 (J = 1 stays bit-exact).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops


@dataclasses.dataclass
class VerifyResult:
    """Outcome of one batched verification round.

    accept_counts: (B,) int32 — n_k in [0, L_k]: accepted draft tokens.
    output_tokens: (B, L+1) int32 — accepted tokens + calibrated/bonus token
        at position n_k; positions > n_k are padding (0).
    output_len:    (B,) int32 — n_k + 1 (paper: N_k, includes the extra token).
    accept_mask:   (B, L) bool — per-position Bernoulli outcomes A_{k,l}.
    routing:       (2,) int32 or None — the target pass's routing counts
        (routed pairs, experts touched) for sigmoid-routed experts.
    """

    accept_counts: jax.Array
    output_tokens: jax.Array
    output_len: jax.Array
    accept_mask: jax.Array
    routing: jax.Array | None = None


# pytree registration: jitted round steps (serving/compiled.py) return a
# VerifyResult straight through the jit boundary
jax.tree_util.register_dataclass(
    VerifyResult,
    data_fields=["accept_counts", "output_tokens", "output_len",
                 "accept_mask", "routing"],
    meta_fields=[])


@dataclasses.dataclass
class TreeVerifyResult(VerifyResult):
    """Outcome of one batched TREE verification round (multi-draft).

    Same commit surface as ``VerifyResult`` (``accept_counts`` /
    ``output_tokens`` / ``output_len`` refer to the LONGEST accepted
    root-to-leaf path), except ``accept_mask`` is per NODE (B, W) — the
    Bernoulli outcome of every tree node's accept test — and ``winner``
    names the draft whose path was committed.
    """

    winner: jax.Array = None        # (B,) int32 winning draft index
    node_valid: jax.Array = None    # (B, W) bool live-node mask


jax.tree_util.register_dataclass(
    TreeVerifyResult,
    data_fields=["accept_counts", "output_tokens", "output_len",
                 "accept_mask", "routing", "winner", "node_valid"],
    meta_fields=[])


def sparse_to_dense(idx: jax.Array, val: jax.Array, vocab: int) -> jax.Array:
    """Scatter top-|V^hat| (idx, val) rows into dense (.., V) distributions."""
    out = jnp.zeros(idx.shape[:-1] + (vocab,), val.dtype)
    return _scatter_last(out, idx, val)


def _scatter_last(out, idx, val):
    grids = jnp.meshgrid(*[jnp.arange(s) for s in idx.shape[:-1]], indexing="ij")
    grids = tuple(g[..., None] for g in grids)
    return out.at[grids + (idx,)].add(val)


def truncate_renormalize(probs: jax.Array, k: int):
    """Top-k truncation + renormalization of draft distributions (paper Sec.
    II-B uplink compression).  Returns (idx (.., k), val (.., k))."""
    val, idx = jax.lax.top_k(probs, k)
    val = val / jnp.sum(val, axis=-1, keepdims=True)
    return idx, val


def verify_drafts(key: jax.Array,
                  draft_tokens: jax.Array,     # (B, L) int32
                  draft_probs: jax.Array,      # (B, L) p_S of each drafted token
                  target_logits: jax.Array,    # (B, L+1, V) LLM logits
                  q_dense: jax.Array | None = None,    # (B, L, V) SLM dists
                  q_idx: jax.Array | None = None,      # (B, L, Vhat) sparse form
                  q_val: jax.Array | None = None,
                  draft_len: jax.Array | None = None,  # (B,) true L_k <= L (zero-pad)
                  ) -> VerifyResult:
    """Batched verification of K drafts in one pass (paper protocol step 4).

    ``target_logits[:, l]`` must condition on the prefix + draft tokens < l
    (the engine produces this with one forward_window call).  With
    heterogeneous draft lengths, rows are zero-padded to L = max L_k and
    ``draft_len`` marks each row's true length; padded positions are forced
    to rejection-impossible (they are simply never accepted).
    """
    B, L = draft_tokens.shape
    V = target_logits.shape[-1]
    k_accept, k_resid, k_bonus = jax.random.split(key, 3)
    u = jax.random.uniform(k_accept, (B, L))
    u_resid = jax.random.uniform(k_resid, (B,))

    if q_dense is None:
        # Sparse uplink-compressed SLM rows (the engine hot path): the
        # accept test + prefix count + calibrated residual token run as ONE
        # fused dispatch — the dense residual distribution never
        # materializes between ops (eq. 4 + eq. 5 in one kernel).
        accept, n_acc, calibrated = kops.fused_verify_sample(
            target_logits, draft_tokens, draft_probs, q_idx, q_val,
            u, u_resid, draft_len)
    else:
        # p_L(x_l) for every drafted position — fused softmax+gather kernel.
        flat_logits = target_logits[:, :L].reshape(B * L, V)
        p_target = kops.gather_softmax_prob(
            flat_logits, draft_tokens.reshape(B * L)).reshape(B, L)

        ratio = p_target / jnp.maximum(draft_probs, 1e-30)
        accept = u < jnp.minimum(ratio, 1.0)                  # eq. 4
        if draft_len is not None:
            accept = accept & (jnp.arange(L)[None, :] < draft_len[:, None])
        prefix_ok = jnp.cumprod(accept.astype(jnp.int32), axis=-1)
        n_acc = jnp.sum(prefix_ok, axis=-1)                   # first-rej index

        # --- calibrated residual sample at the first rejection (eq. 5) ---
        sel = jnp.minimum(n_acc, L - 1)
        logits_rej = jnp.take_along_axis(
            target_logits, sel[:, None, None], axis=1)[:, 0]  # (B, V)
        p_rej = jax.nn.softmax(logits_rej.astype(jnp.float32), axis=-1)
        q_rej = jnp.take_along_axis(q_dense, sel[:, None, None], axis=1)[:, 0]
        calibrated = kops.residual_sample(p_rej, q_rej, u_resid)  # (B,)

    # --- bonus token when the whole draft is accepted ---
    true_len = draft_len if draft_len is not None else jnp.full((B,), L)
    logits_bonus = jnp.take_along_axis(
        target_logits, true_len[:, None, None], axis=1)[:, 0]
    bonus = jax.random.categorical(k_bonus, logits_bonus.astype(jnp.float32),
                                   axis=-1).astype(jnp.int32)

    full_accept = n_acc >= true_len
    extra = jnp.where(full_accept, bonus, calibrated)

    # --- assemble outputs: draft[:n] + extra at position n ---
    pos = jnp.arange(L + 1)[None, :]
    n_col = n_acc[:, None]
    padded_draft = jnp.pad(draft_tokens, ((0, 0), (0, 1)))
    out = jnp.where(pos < n_col, padded_draft,
                    jnp.where(pos == n_col, extra[:, None], 0)).astype(jnp.int32)

    return VerifyResult(accept_counts=n_acc.astype(jnp.int32),
                        output_tokens=out,
                        output_len=(n_acc + 1).astype(jnp.int32),
                        accept_mask=accept)


def verify_tree(key: jax.Array,
                tree_tokens: jax.Array,      # (B, W) node tokens
                tree_parents: jax.Array,     # (B, W) parent idx (-1 root, -2 dead)
                tree_depth: jax.Array,       # (B, W) 1-based depth (0 dead)
                tree_probs: jax.Array,       # (B, W) p_S of each node token
                paths: jax.Array,            # (B, J, L) node idx per draft pos
                target_logits: jax.Array,    # (B, W+1, V) tree-window logits
                q_idx: jax.Array,            # (B, W, Vhat) sparse SLM dists
                q_val: jax.Array,
                draft_len: jax.Array,        # (B,) true L_k <= L
                ) -> TreeVerifyResult:
    """Batched token-tree verification (multi-draft protocol step 4).

    ``target_logits`` must come from ONE ancestor-masked window pass over
    [pending, node_0, ...]: the logits at a node's PARENT slot condition on
    exactly the root-to-parent path, so every node runs the standard accept
    test (eq. 4) in parallel.  The committed output is the LONGEST accepted
    root-to-leaf path (ties -> first draft), closed by the calibrated
    residual token at its first rejection (eq. 5) or a bonus token on full
    acceptance — i.e. the engine realization of the ``multidraft`` scheme's
    max-of-J acceptance model.

    At J = 1 (every tree a chain) this consumes the exact rng stream of
    ``verify_drafts`` and commits bit-identical tokens — the exactness
    guarantee of sequential speculative sampling is fully preserved.

    At J > 1 this is deliberately the scheme's MAX-OF-J law, not exact
    multi-draft speculative sampling: each node runs the unmodified
    min(1, p_L/p_S) test, so accepting a sibling after another sibling's
    rejection does NOT discount the residual the way SpecTr/SpecInfer's
    sequential-sibling scheme does, and the committed per-position
    distribution tilts toward draft-supported tokens (e.g. J=2, L=1,
    q=(.5,.5,0), p=(0,.5,.5) commits (0,.75,.25)).  That is the acceptance
    model the paper's ``multidraft`` goodput analysis and the
    ``SyntheticBackend`` assume (E[N] = 1 + sum_l 1-(1-a^l)^J) — parity
    with it is what the engine tests assert.
    """
    B, W = tree_tokens.shape
    V = target_logits.shape[-1]
    J, L = paths.shape[1], paths.shape[2]
    k_accept, k_resid, k_bonus = jax.random.split(key, 3)

    # p_L(token_i | path to parent): logits at each node's parent slot
    # (root parent = pending at slot 0; node i sits at slot i + 1).
    parent_slot = jnp.where(tree_parents >= 0, tree_parents + 1, 0)
    logits_par = jnp.take_along_axis(target_logits, parent_slot[:, :, None],
                                     axis=1)                  # (B, W, V)
    p_target = kops.gather_softmax_prob(
        logits_par.reshape(B * W, V),
        tree_tokens.reshape(B * W)).reshape(B, W)

    ratio = p_target / jnp.maximum(tree_probs, 1e-30)
    u = jax.random.uniform(k_accept, (B, W))
    valid = (tree_depth >= 1) & (tree_depth <= draft_len[:, None])
    accept = (u < jnp.minimum(ratio, 1.0)) & valid            # per NODE

    # per-path acceptance: shared prefixes share their nodes' outcomes
    safe_paths = jnp.maximum(paths, 0).reshape(B, J * L)
    acc_path = jnp.take_along_axis(
        accept.astype(jnp.int32), safe_paths, axis=1).reshape(B, J, L)
    acc_path = jnp.where(paths >= 0, acc_path, 0)
    prefix_ok = jnp.cumprod(acc_path, axis=-1)
    n_path = jnp.sum(prefix_ok, axis=-1)                      # (B, J)
    n_acc = jnp.max(n_path, axis=-1)
    winner = jnp.argmax(n_path, axis=-1).astype(jnp.int32)    # first max

    path_w = jnp.take_along_axis(
        paths, winner[:, None, None], axis=1)[:, 0]           # (B, L)

    # --- calibrated residual at the winner's first rejected node (eq. 5) ---
    sel = jnp.minimum(n_acc, L - 1)
    rej_node = jnp.take_along_axis(path_w, sel[:, None], axis=1)[:, 0]
    rej_node = jnp.maximum(rej_node, 0)     # past-length rows: bonus wins below
    rej_slot = jnp.take_along_axis(parent_slot, rej_node[:, None], axis=1)[:, 0]
    logits_rej = jnp.take_along_axis(target_logits, rej_slot[:, None, None],
                                     axis=1)[:, 0]            # (B, V)
    p_rej = jax.nn.softmax(logits_rej.astype(jnp.float32), axis=-1)
    idx_rej = jnp.take_along_axis(q_idx, rej_node[:, None, None], axis=1)[:, 0]
    val_rej = jnp.take_along_axis(q_val, rej_node[:, None, None], axis=1)[:, 0]
    q_rej = _scatter_last(jnp.zeros((B, V), jnp.float32), idx_rej,
                          val_rej.astype(jnp.float32))
    u_resid = jax.random.uniform(k_resid, (B,))
    calibrated = kops.residual_sample(p_rej, q_rej, u_resid)  # (B,)

    # --- bonus token when the winner's whole draft is accepted ---
    last = jnp.maximum(draft_len - 1, 0)
    last_node = jnp.take_along_axis(path_w, last[:, None], axis=1)[:, 0]
    bonus_slot = jnp.maximum(last_node, 0) + 1
    logits_bonus = jnp.take_along_axis(target_logits, bonus_slot[:, None, None],
                                       axis=1)[:, 0]
    bonus = jax.random.categorical(k_bonus, logits_bonus.astype(jnp.float32),
                                   axis=-1).astype(jnp.int32)

    full_accept = n_acc >= draft_len
    extra = jnp.where(full_accept, bonus, calibrated)

    # --- assemble outputs: winner path[:n] + extra at position n ---
    path_tokens = jnp.take_along_axis(tree_tokens, jnp.maximum(path_w, 0),
                                      axis=1)                 # (B, L)
    pos = jnp.arange(L + 1)[None, :]
    n_col = n_acc[:, None]
    padded_path = jnp.pad(path_tokens, ((0, 0), (0, 1)))
    out = jnp.where(pos < n_col, padded_path,
                    jnp.where(pos == n_col, extra[:, None], 0)).astype(jnp.int32)

    return TreeVerifyResult(accept_counts=n_acc.astype(jnp.int32),
                            output_tokens=out,
                            output_len=(n_acc + 1).astype(jnp.int32),
                            accept_mask=accept,
                            winner=winner,
                            node_valid=valid)
