"""Compiled round path: jitted draft/verify/commit step functions.

The engine's three row-subset round steps (``draft_rows`` / ``verify_rows``
/ ``commit_rows``) bottom out in the pure step functions built here.  Each
step takes the model params, the KV-cache pytree, the (non-donated) page
table and the stream-state arrays as ARGUMENTS — nothing round-varying is
closure-captured — so ``jax.jit`` can alias the donated buffers:

  * ``draft_step``  — donates the DRAFT KV cache (argnum 1)
  * ``verify_step`` — donates the TARGET KV cache (argnum 1)
  * ``commit_step`` — donates pending / target_pos / draft_pos (0, 1, 2)

and the token-tree round's (``multidraft``, J > 1) three steps:

  * ``forest_step``      — J drafting runs; donates the DRAFT KV cache
  * ``tree_verify_step`` — ancestor-masked target pass + tree accept test;
                           donates the TARGET KV cache
  * ``tree_commit_step`` — scatter-commit of the winning branch's K/V;
                           donates both KV caches

and the paged admission prefill:

  * ``prefill_step`` — both models' prefill of the admitted prompts at one
                       prompt bucket; donates both KV caches (argnum 1)
                       and returns only the caches, so the final norm and
                       the vocabulary projection over every prompt
                       position are never computed (a target with
                       sigmoid-routed experts adds its routing counts).  The eager prefill
                       remains for ``eager`` mode, the contiguous
                       ``start()`` and ``serving/engine.py``.

Donation invariants (docs/architecture.md "compilation & memory model"):

  * a donated buffer is DEAD after the call — the engine adopts the
    returned cache/state pytree and must never re-read the old reference;
  * the page-table array is never donated: the allocator's persistent
    device mirror (``PagedKVCache.device_table``) keeps a live reference
    across rounds;
  * step functions strip the ``"pages"`` entry from the cache they return,
    so a stale page table can never ride along inside an adopted cache.

Shapes are keyed at the same pow2 (batch, length) buckets the continuous
engine's ``BatchAssembler`` emits, and the prefill at the engine's pow2
prompt buckets, which bounds retraces; the ``record`` hook fires only at
TRACE time (python inside a jitted body), mirroring the
``BatchAssembler.shapes`` accounting idiom, so tests can assert the
retrace count.
"""

from __future__ import annotations

import dataclasses
import os
from pathlib import Path
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.drafting import generate_draft_forest, generate_drafts
from repro.core.verification import verify_drafts, verify_tree
from repro.models.layers import gather_kv_window, scatter_kv_window
from repro.models.transformer import strip_view

COMPILE_MODES = ("eager", "jit", "jit+donate")


_REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compilation_cache() -> str | None:
    """Turn on JAX's persistent compilation cache for an entry point.

    The directory is ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
    reads the variable itself; nothing here overrides it), else the fixed
    ``<repo root>/.jax_cache``: the path is part of the cache key, so it is
    never built from a temp name, a pid or the time.  Without the variable
    a CPU backend gets no cache, so tests and CPU runs write nothing into
    the checkout.  Returns the directory in use, or None.

    Cold gateway starts compile the full round path (minutes at real
    shapes); with the cache a restart at the same shapes loads the
    executables from disk, so every round-step executable is persisted.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        if jax.default_backend() == "cpu":
            return None
        cache_dir = str(_REPO_CACHE)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def commit_step(pending: jax.Array, target_pos: jax.Array,
                draft_pos: jax.Array, rows: jax.Array, skip: jax.Array,
                output_tokens: jax.Array, accept_counts: jax.Array):
    """Row-subset commit, entirely on device.

    rows: (n,) int32 state-row index per ticket slot, ``-1`` = padding.
    skip: (n,) bool — padding / frozen / retired slots commit nothing.
    output_tokens: (n, L+1); accept_counts: (n,).

    Updates ONLY the affected rows of the (B,) state arrays — padding maps
    to row 0 with a zero delta, and because integer scatter-add of zeros is
    exact, duplicated padding rows are harmless (live rows are distinct by
    the engine's one-live-ticket-per-row invariant).  Returns the new state
    arrays plus a packed ``(n, L+2)`` int32 emission —
    ``[advance, output_tokens...]`` per slot — which is the ONE device->host
    fetch the engine performs per round.
    """
    safe = jnp.where(rows < 0, 0, rows)
    k = accept_counts.astype(jnp.int32)
    adv = jnp.where(skip, 0, k + 1).astype(jnp.int32)
    new_tok = jnp.take_along_axis(output_tokens, k[:, None], axis=1)[:, 0]
    old = jnp.take(pending, safe)
    delta = jnp.where(skip, 0, new_tok.astype(pending.dtype) - old)
    pending = pending.at[safe].add(delta)
    target_pos = target_pos.at[safe].add(adv)
    draft_pos = draft_pos.at[safe].add(adv)
    emission = jnp.concatenate(
        [adv[:, None], output_tokens.astype(jnp.int32)], axis=1)
    return pending, target_pos, draft_pos, emission


def tree_scatter_commit(t_cache, d_cache, windows, paths, winner,
                        accept_counts, frozen, target_pos, draft_pos):
    """Land the accepted tree branch's K/V in its committed slots.

    The ancestor-masked target pass already computed every node's K/V
    conditioned on exactly its root-to-node path, so the winning branch's
    rows move from their tree-window slots into slots [pos + 1, pos + n];
    the draft side takes them from the winning run's window snapshot
    (``DraftForest.windows``).  Caches are views (``"pages"`` kept when
    present); returns the updated (t_cache, d_cache).
    """
    L = paths.shape[2]
    path_w = jnp.take_along_axis(paths, winner[:, None, None],
                                 axis=1)[:, 0]                  # (B, L)
    keep = ((jnp.arange(L)[None, :] < accept_counts[:, None])
            & (path_w >= 0) & ~frozen[:, None])
    col = jnp.arange(L, dtype=jnp.int32)[None, :]
    src_t = target_pos[:, None] + 1 + jnp.maximum(path_w, 0)
    dst_t = target_pos[:, None] + 1 + col
    dst_d = draft_pos[:, None] + 1 + col
    t_cache, d_cache = dict(t_cache), dict(d_cache)
    t_pt, d_pt = t_cache.get("pages"), d_cache.get("pages")
    for leaf in windows:
        vals = gather_kv_window(t_cache[leaf], src_t, page_table=t_pt)
        t_cache[leaf] = scatter_kv_window(t_cache[leaf], vals, dst_t, keep,
                                          page_table=t_pt)
        win = jnp.take_along_axis(
            windows[leaf], winner[None, :, None, None, None, None],
            axis=2)[:, :, 0]                                   # (Ln,B,L,KV,D)
        d_cache[leaf] = scatter_kv_window(d_cache[leaf], win, dst_d, keep,
                                          page_table=d_pt)
    return t_cache, d_cache


@dataclasses.dataclass
class RoundSteps:
    """The compiled (or eager) step callables for one engine.

    ``draft`` / ``verify``, the tree steps ``forest`` / ``tree_verify``
    / ``tree_commit`` and the admission ``prefill`` are None in eager mode
    — the engine keeps its op-by-op dispatch path; ``commit`` is always
    callable (the eager path shares the same device-side commit math, just
    unjitted).
    """

    mode: str
    draft: Callable | None
    verify: Callable | None
    commit: Callable
    forest: Callable | None = None
    tree_verify: Callable | None = None
    tree_commit: Callable | None = None
    prefill: Callable | None = None


def build_round_steps(target_model, draft_model, *, mode: str,
                      record: Callable[[tuple], None] | None = None,
                      ) -> RoundSteps:
    """Build the round-step callables for a (target, draft) model pair.

    ``record`` is invoked with a ``(step, B, L)`` shape key inside each
    function body — under ``jit`` that python runs at trace time only, so
    the callback counts RETRACES, not calls.
    """
    if mode not in COMPILE_MODES:
        raise ValueError(f"compile_mode must be one of {COMPILE_MODES}, "
                         f"got {mode!r}")
    donate = mode == "jit+donate"
    routed = getattr(target_model, "routed", False)

    def _record(kind: str, n: int, L: int):
        if record is not None:
            record((kind, n, L))

    def draft_step(params, kv, pages, pending, dpos, key, *, L, vhat):
        _record("draft", pending.shape[0], L)
        cache = kv if pages is None else dict(kv, pages=pages)
        res = generate_drafts(draft_model, params, cache, pending, dpos,
                              L, key, vhat=vhat)
        return dataclasses.replace(res, cache=strip_view(res.cache))

    def verify_step(params, kv, pages, pending, tokens, probs, q_idx,
                    q_val, tpos, draft_len, key):
        _record("verify", tokens.shape[0], tokens.shape[1])
        cache = kv if pages is None else dict(kv, pages=pages)
        window = jnp.concatenate([pending[:, None], tokens], axis=1)
        if routed:
            logits, cache, routing = target_model.forward_window(
                params, window, cache, tpos, return_aux=True)
        else:
            logits, cache = target_model.forward_window(params, window,
                                                        cache, tpos)
        res = verify_drafts(key, tokens, probs, logits, q_idx=q_idx,
                            q_val=q_val, draft_len=draft_len)
        if routed:
            res = dataclasses.replace(res, routing=routing)
        return res, strip_view(cache)

    def commit(pending, target_pos, draft_pos, rows, skip, output_tokens,
               accept_counts):
        _record("commit", rows.shape[0], output_tokens.shape[1] - 1)
        return commit_step(pending, target_pos, draft_pos, rows, skip,
                           output_tokens, accept_counts)

    def forest_step(params, kv, pages, pending, dpos, key, *, L, J, vhat,
                    keep_windows):
        _record("forest", pending.shape[0], L)
        cache = kv if pages is None else dict(kv, pages=pages)
        forest = generate_draft_forest(draft_model, params, cache, pending,
                                       dpos, L, J, key, vhat=vhat,
                                       keep_windows=keep_windows)
        return dataclasses.replace(forest, cache=strip_view(forest.cache))

    def tree_verify_step(params, kv, pages, window, wmask, wdepth, tpos, key,
                         tokens, parents, depth, probs, paths, q_idx, q_val,
                         draft_len):
        _record("tree_verify", tokens.shape[0], paths.shape[2])
        cache = kv if pages is None else dict(kv, pages=pages)
        logits, cache = target_model.forward_window(
            params, window, cache, tpos, window_mask=wmask,
            window_depth=wdepth)
        res = verify_tree(key, tokens, parents, depth, probs, paths, logits,
                          q_idx, q_val, draft_len)
        return res, strip_view(cache)

    def tree_commit_step(t_kv, t_pages, d_kv, d_pages, windows, paths,
                         winner, accept_counts, frozen, tpos, dpos):
        _record("tree_commit", paths.shape[0], paths.shape[2])
        t_cache = t_kv if t_pages is None else dict(t_kv, pages=t_pages)
        d_cache = d_kv if d_pages is None else dict(d_kv, pages=d_pages)
        t_cache, d_cache = tree_scatter_commit(
            t_cache, d_cache, windows, paths, winner, accept_counts, frozen,
            tpos, dpos)
        return strip_view(t_cache), strip_view(d_cache)

    def prefill_step(params, kv, pages, tokens):
        # params / kv / pages are (target, draft) pairs; tokens (n, Mb) is
        # the bucket-padded prompt batch, whose last token stays pending
        _record("prefill", tokens.shape[0], tokens.shape[1])
        caches = []
        for model, p, c, pt in zip((target_model, draft_model), params, kv,
                                   pages):
            _, cache, aux = model.prefill(p, tokens[:, :-1],
                                          dict(c, pages=pt))
            caches.append(strip_view(cache))
            if model is target_model and routed:
                routing = aux
        if routed:
            return caches[0], caches[1], routing
        return tuple(caches)

    if mode == "eager":
        return RoundSteps(mode=mode, draft=None, verify=None,
                          commit=commit_step)
    return RoundSteps(
        mode=mode,
        draft=jax.jit(draft_step, static_argnames=("L", "vhat"),
                      donate_argnums=(1,) if donate else ()),
        verify=jax.jit(verify_step,
                       donate_argnums=(1,) if donate else ()),
        commit=jax.jit(commit, donate_argnums=(0, 1, 2) if donate else ()),
        forest=jax.jit(forest_step,
                       static_argnames=("L", "J", "vhat", "keep_windows"),
                       donate_argnums=(1,) if donate else ()),
        tree_verify=jax.jit(tree_verify_step,
                            donate_argnums=(1,) if donate else ()),
        tree_commit=jax.jit(tree_commit_step,
                            donate_argnums=(0, 2) if donate else ()),
        prefill=jax.jit(prefill_step,
                        donate_argnums=(1,) if donate else ()),
    )
