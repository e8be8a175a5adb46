"""Speculative decoding engine: SLM drafting + LLM batched verification on
real model weights (the compute core of Multi-SPIN, paper Fig. 1).

One ``SpecEngine`` drives B concurrent streams (one per edge device).  Each
round (paper Fig. 2):

  2. drafting       — ``generate_drafts`` on the draft model (per-stream
                       heterogeneous lengths, zero-padded to the window)
  4. verification   — ONE ``forward_window`` of the target model over
                       [pending, d_1 .. d_L] followed by exact accept/reject
                       (``verify_drafts``)
  5. state update   — pointer arithmetic for attention caches; snapshot
                       rollback for SSM state

Two cache layouts:

  * ``cache_kind="contiguous"`` — the classic (B, max_len) slabs fixed at
    ``start()``; the stream population can never change.
  * ``cache_kind="paged"``      — both models' KV lives in fixed-size pages
    of a preallocated pool (``PagedKVCache``); streams may join after
    ``start()`` (``add_streams``) and leave (``retire_stream``), rejected
    speculative tokens return their pages each round, and admission is
    bounded only by the page pool (``can_admit``).

The engine is deliberately network-free: the cell layer wraps it with the
channel/latency model to produce goodput numbers.
"""

from __future__ import annotations

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.drafting import generate_draft_forest, generate_drafts
from repro.core.token_tree import TreeScratch, build_token_tree
from repro.core.verification import verify_drafts, verify_tree
from repro.models import build_model
from repro.models.transformer import strip_view
from repro.obs import trace

from .compiled import COMPILE_MODES, build_round_steps, tree_scatter_commit
from .kv_cache import (
    PagedKVCache,
    PagePoolExhausted,
    cache_bytes,
    merge_snapshot_into_cache,
    needs_state_rollback,
    paged_pool_bytes_per_page,
    select_snapshots,
)

CACHE_KINDS = ("contiguous", "paged")
_NO_MLA_TREE = ("token-tree verification (draft_width > 1) with an MLA "
                "target needs a paged tree latent-attention path, which the "
                "engine does not have")


def _span(name: str, args: dict | None = None):
    """Engine-phase span (``cat="engine"``).  These fire once per round (not
    per dispatched op), so the few args dicts built per round are noise; the
    per-op hot path in ``kernels/ops.py`` has the strict zero-allocation
    guard."""
    if trace.active() is None:
        return trace.NULL_SPAN
    return trace.span(name, cat="engine", args=args)


@dataclasses.dataclass
class StreamState:
    """Per-batch serving state (B streams)."""

    pending: jax.Array        # (B,) last committed token, not yet in caches
    target_pos: jax.Array     # (B,) target-cache fill level
    draft_pos: jax.Array      # (B,) draft-cache fill level
    committed: list           # python-side committed token lists (B)


@dataclasses.dataclass
class RoundTicket:
    """In-flight round work for a row subset, between dispatch and commit.

    Produced by ``draft_rows``, completed by ``verify_rows``, consumed by
    ``commit_rows``.  Everything on it is either host metadata or an
    ASYNCHRONOUSLY dispatched jax array — holding a ticket never blocks, so
    a continuous-batching driver can dispatch the next round's drafting
    while this ticket's verification is still in flight.

    ``rows`` may contain ``-1`` padding entries (batch-shape bucketing):
    their page-table rows are all ``-1`` — cache writes dropped, reads
    masked — and ``commit_rows`` skips them unconditionally.
    """

    rows: list | None             # engine rows; None = the full batch
    lengths: np.ndarray           # per-row planned draft lengths
    L: int                        # the dispatched window length (max/bucket)
    freeze: np.ndarray            # per-row do-not-advance mask
    pending: jax.Array            # (n,) pending tokens at dispatch
    target_pos: jax.Array         # (n,) target positions at dispatch
    draft: object | None = None   # DraftResult from generate_drafts
    res: object | None = None     # VerifyResult from verify_drafts


class SpecEngine:
    """Speculative-decoding engine for B device streams: a small draft
    model proposes tokens, a large target model batch-verifies them, and
    both models' KV caches advance only over committed tokens.

    ``spin_round`` is one protocol round.  ``draft_width`` J > 1 runs
    token-TREE verification: J i.i.d. drafts per stream packed into a
    prefix-deduplicated trie, scored in ONE ancestor-masked target pass,
    with the longest accepted root-to-leaf path committed.  The row-subset
    API (``draft_rows`` / ``verify_rows`` / ``commit_rows``) exposes the
    same round as async pieces for continuous batching.

    ``cache_kind``: ``"contiguous"`` fixes the batch at ``start()``;
    ``"paged"`` serves churn from a pooled ``PagedKVCache`` (streams join
    after start, retire, recycle rows).  Attention over either layout
    dispatches through the Pallas kernel ops when ``REPRO_KERNELS`` selects
    them (docs/kernels.md).

    ``tree_commit``: how accepted tree branches reach the cache.
    ``"scatter"`` (default) gathers the winning branch's K/V from the
    already-written tree window and scatters it to contiguous positions —
    no extra forward pass (span ``engine.kv_commit``); ``"repair"`` keeps
    the reference re-forward over ``[pending, accepted path]`` (span
    ``engine.cache_repair``).  Both commit identical tokens at the same
    seed (tested, and asserted by ``bench_beyond --engine``).

    ``draft_width``: the widest token tree the engine will be asked to
    verify.  An MLA target refuses trees (> 1) here, and in every tree
    round."""

    def __init__(self, target_cfg: ModelConfig, draft_cfg: ModelConfig,
                 max_len: int = 512, cache_dtype=jnp.float32,
                 cache_kind: str = "contiguous", page_size: int = 16,
                 num_pages: int | None = None, tree_commit: str = "scatter",
                 compile_mode: str | None = None, draft_width: int = 1):
        assert target_cfg.vocab_size == draft_cfg.vocab_size, \
            "SLM/LLM pair must share a vocabulary"
        if draft_width > 1 and target_cfg.attention == "mla":
            raise NotImplementedError(_NO_MLA_TREE)
        if cache_kind not in CACHE_KINDS:
            raise ValueError(f"cache_kind must be one of {CACHE_KINDS}")
        if tree_commit not in ("scatter", "repair"):
            raise ValueError("tree_commit must be 'scatter' or 'repair'")
        if cache_kind == "paged" and (needs_state_rollback(target_cfg)
                                      or needs_state_rollback(draft_cfg)):
            raise NotImplementedError(
                "paged caches cover attention KV only; SSM/hybrid recurrent "
                "state is O(1) per stream and needs no paging (ROADMAP)")
        if compile_mode is None:
            compile_mode = os.environ.get("REPRO_COMPILE", "eager")
        if compile_mode not in COMPILE_MODES:
            raise ValueError(f"compile_mode must be one of {COMPILE_MODES}, "
                             f"got {compile_mode!r}")
        if compile_mode != "eager" and (needs_state_rollback(target_cfg)
                                        or needs_state_rollback(draft_cfg)):
            raise NotImplementedError(
                "compiled round steps cover attention models; SSM/hybrid "
                "snapshot rollback re-enters python between the target pass "
                "and the cache merge (ROADMAP open items)")
        self.target_cfg = target_cfg
        self.draft_cfg = draft_cfg
        self.target = build_model(target_cfg)
        self.draft = build_model(draft_cfg)
        # sigmoid-routed experts: the target passes' routing counts ride the
        # fetches a round's commit and an admission already make
        self.routed = getattr(self.target, "routed", False)
        self.max_len = max_len
        self.cache_dtype = cache_dtype
        self.cache_kind = cache_kind
        self.tree_commit = tree_commit
        self.page_size = int(page_size)
        self.pages_per_stream = -(-max_len // self.page_size)
        self.num_pages = num_pages
        self.t_params = None
        self.d_params = None
        self.t_pages: PagedKVCache | None = None
        self.d_pages: PagedKVCache | None = None
        self._free_rows: list[int] = []
        self._retired: set[int] = set()
        # compile accounting: every paged-prefill (n, Mb) shape traced —
        # at trace time under jit, on every call in eager mode (where each
        # call traces the layer scan anew); tests hook ``on_prefill_trace``
        self.prefill_shapes: set[tuple[int, int]] = set()
        self.on_prefill_trace = None
        # compiled round path: jitted draft/verify/commit step functions
        # (serving/compiled.py).  ``step_shapes`` collects every (step, B, L)
        # actually TRACED — the hook fires from inside the jitted bodies, so
        # it counts retraces, not calls; ``warmup()`` pre-seeds the buckets.
        self.compile_mode = compile_mode
        self.step_shapes: set[tuple] = set()
        self.on_step_trace = None
        self._steps = build_round_steps(self.target, self.draft,
                                        mode=compile_mode,
                                        record=self._record_step)
        # host-transfer accounting: every blocking device->host fetch on the
        # round path funnels through ``_host_fetch`` and bumps this
        self.host_syncs = 0
        # host-side accepted counts of the last FULL-BATCH commit (the
        # lockstep backend reads these instead of re-fetching output_len);
        # None after row-subset commits, whose alignment is ticket-local
        self.last_accepted: np.ndarray | None = None
        self._tree_scratch = TreeScratch()

    def _record_step(self, shape: tuple) -> None:
        self.step_shapes.add(shape)
        if self.on_step_trace is not None:
            self.on_step_trace(shape)
        if shape[0] == "prefill":
            self._record_prefill(shape[1], shape[2])

    def _record_prefill(self, n: int, Mb: int) -> None:
        """One prefill trace at ``(n, Mb)``; admissions
        (``engine.admit_host_sync``) minus ``engine.prefill_trace`` is the
        number served without one."""
        self.prefill_shapes.add((n, Mb))
        if self.on_prefill_trace is not None:
            self.on_prefill_trace((n, Mb))
        trace.incr("engine.prefill_trace")

    def _host_fetch(self, value):
        """Blocking device->host fetch.  The ONE per-round call site is the
        packed commit emission in ``commit_rows``; tree rounds add the
        host-side trie build and (repair mode) the accepted-depth fetch.
        Counting every fetch here keeps ``RoundRecord.n_host_syncs``
        honest."""
        self.host_syncs += 1
        trace.incr("engine.host_sync")
        return jax.device_get(value)

    def _count_routing(self, counts) -> None:
        """Add one target pass's routing counts (host values, summed over
        its MoE layers) to the tracer's counters: ``engine.moe_routed_pairs``
        (token, held expert) pairs computed, ``engine.moe_experts_touched``
        held experts that got a token, ``engine.moe_layer_passes`` MoE layers
        run."""
        cfg = self.target_cfg
        trace.incr("engine.moe_routed_pairs", int(counts[0]))
        trace.incr("engine.moe_experts_touched", int(counts[1]))
        trace.incr("engine.moe_layer_passes",
                   cfg.num_layers - cfg.first_k_dense)

    # ------------------------------------------------------------------

    def init_params(self, key):
        """Random weights for both models, each built by one jitted program
        directly in its config's ``param_dtype``: the device never holds a
        float32 or per-layer copy beside them.  Same values as the eager
        ``init``."""
        kt, kd = jax.random.split(key)
        self.t_params = jax.jit(self.target.init)(kt)
        self.d_params = jax.jit(self.draft.init)(kd)
        return self.t_params, self.d_params

    def start(self, prompts: jax.Array) -> StreamState:
        """Prefill both models on the prompts (B, M).  The last prompt token
        becomes the pending token (its logits seed round 1).

        Paged engines size the pool here when ``num_pages`` was not given:
        2x the pages the start batch needs at max_len, so churn has headroom
        by default."""
        B, M = prompts.shape
        self._free_rows, self._retired = [], set()
        if self.cache_kind == "paged":
            if self.num_pages is None:
                self.num_pages = 2 * B * self.pages_per_stream
            self.t_cache = self.target.init_paged_cache(
                self.num_pages, self.page_size, self.cache_dtype)
            self.d_cache = self.draft.init_paged_cache(
                self.num_pages, self.page_size, self.cache_dtype)
            self.t_pages = PagedKVCache(
                self.num_pages, self.page_size, self.pages_per_stream,
                paged_pool_bytes_per_page(self.t_cache))
            self.d_pages = PagedKVCache(
                self.num_pages, self.page_size, self.pages_per_stream,
                paged_pool_bytes_per_page(self.d_cache))
            state = StreamState(
                pending=jnp.zeros((0,), prompts.dtype),
                target_pos=jnp.zeros((0,), jnp.int32),
                draft_pos=jnp.zeros((0,), jnp.int32),
                committed=[])
            state, _ = self.add_streams(state, prompts)
            return state
        self.t_cache = self.target.init_cache(B, self.max_len, self.cache_dtype)
        self.d_cache = self.draft.init_cache(B, self.max_len, self.cache_dtype)
        _, self.t_cache, _ = self.target.prefill(self.t_params, prompts[:, :-1],
                                                 self.t_cache)
        _, self.d_cache, _ = self.draft.prefill(self.d_params, prompts[:, :-1],
                                                self.d_cache)
        return StreamState(
            pending=prompts[:, -1],
            target_pos=jnp.full((B,), M - 1, jnp.int32),
            draft_pos=jnp.full((B,), M - 1, jnp.int32),
            committed=[list(np.asarray(prompts[b])) for b in range(B)],
        )

    # ------------------------------------------------------------------
    # dynamic stream admission (paged only)
    # ------------------------------------------------------------------

    def can_admit(self, length: int) -> bool:
        """Whether BOTH page pools can map a new stream of ``length`` tokens
        right now — the admission-control predicate (OOM-safe: pure query)."""
        if self.cache_kind != "paged":
            return False
        return (self.t_pages.can_allocate(length)
                and self.d_pages.can_allocate(length))

    def pool_stats(self) -> dict:
        """Byte-level accounting for placement / admission decisions."""
        if self.cache_kind != "paged":
            return {"cache_bytes": cache_bytes(self.t_cache)
                    + cache_bytes(self.d_cache)}
        return {
            "cache_bytes": cache_bytes(self.t_cache) + cache_bytes(self.d_cache),
            "free_bytes": self.t_pages.free_bytes() + self.d_pages.free_bytes(),
            "used_bytes": self.t_pages.used_bytes() + self.d_pages.used_bytes(),
            "free_pages": min(self.t_pages.num_free_pages,
                              self.d_pages.num_free_pages),
        }

    def prompt_bucket(self, M: int) -> int:
        """Power-of-two bucket (min 8, capped at ``max_len``) for paged
        prefill shapes.  Joining prompts are right-padded to the bucket so
        heavy churn compiles one XLA prefill trace per bucket instead of
        one per distinct prompt length."""
        b = 8
        while b < M:
            b *= 2
        return max(M, min(b, self.max_len))

    def add_streams(self, state: StreamState, prompts: jax.Array):
        """Admit ``prompts`` (n, M) as new streams AFTER ``start()``.

        Retired batch rows are recycled first; otherwise the batch grows.
        Pages are allocated from the pool (``PagePoolExhausted`` when it is
        truly out of memory — call ``can_admit`` first).  The prefill runs
        at the power-of-two ``prompt_bucket`` shape: pad K/V past the true
        prompt is written but never attended (per-row length masking) and
        its pages return to the pool right after the prefill.  Returns
        ``(new_state, rows)`` with the engine rows assigned in order."""
        if self.cache_kind != "paged":
            raise RuntimeError(
                "contiguous caches are fixed at start(); construct the "
                "engine with cache_kind='paged' to serve churn")
        n, M = prompts.shape
        Mb = self.prompt_bucket(M)
        with _span("engine.add_streams", {"n": n, "M": M, "Mb": Mb}) as sp:
            new_state, rows = self._add_streams(state, prompts, n, M, Mb)
            sp.set(rows=rows)
        return new_state, rows

    def _add_streams(self, state: StreamState, prompts: jax.Array, n: int,
                     M: int, Mb: int):
        B = state.pending.shape[0]
        rows = []
        for _ in range(n):
            row = self._free_rows.pop(0) if self._free_rows else B + len(
                [r for r in rows if r >= B])
            rows.append(row)
        allocated = []
        try:
            with _span("engine.page_alloc"):
                for row in rows:
                    # transiently map the BUCKETED prefill extent; truncated
                    # back to the true prompt right after the prefill below
                    self.t_pages.alloc_stream(row, Mb - 1)
                    allocated.append((self.t_pages, row))
                    self.d_pages.alloc_stream(row, Mb - 1)
                    allocated.append((self.d_pages, row))
        except Exception:
            for mgr, row in allocated:
                mgr.free_stream(row)
            self._free_rows = sorted(set(self._free_rows)
                                     | {r for r in rows if r < B})
            raise
        self._retired -= set(rows)

        # prefill ONLY the new rows; their pages view writes into the pools.
        # Right-pad to the bucket with the last prompt token: padded K/V
        # lands at positions >= M-1, which every later window write covers
        # before attention can reach it (causal mask kj <= position).
        with _span("engine.prompt_pad"):
            if Mb > M:
                pad = jnp.tile(prompts[:, -1:], (1, Mb - M))
                padded = jnp.concatenate([prompts, pad], axis=1)
            else:
                padded = prompts
        routing = None
        if self._steps.prefill is not None:
            # one compiled program per (n, Mb) bucket; in jit+donate the
            # pools are donated and the returned ones adopted
            with _span("engine.prefill"):
                out = self._steps.prefill(
                    (self.t_params, self.d_params),
                    (self.t_cache, self.d_cache),
                    (self.t_pages.device_table(rows),
                     self.d_pages.device_table(rows)), padded)
                self.t_cache, self.d_cache = out[:2]
                if self.routed:
                    routing = out[2]
        else:
            self._record_prefill(n, Mb)
            with _span("engine.prefill", {"model": "target"}):
                t_view = dict(self.t_cache,
                              pages=self.t_pages.device_table(rows))
                _, t_view, aux = self.target.prefill(self.t_params,
                                                     padded[:, :-1], t_view)
                self.t_cache = strip_view(t_view)
                if self.routed:
                    routing = aux
            with _span("engine.prefill", {"model": "draft"}):
                d_view = dict(self.d_cache,
                              pages=self.d_pages.device_table(rows))
                _, d_view, _ = self.draft.prefill(self.d_params,
                                                  padded[:, :-1], d_view)
                self.d_cache = strip_view(d_view)
        if Mb > M:
            # hand the bucket-padding pages straight back to the pool
            for row in rows:
                self.t_pages.truncate(row, M - 1)
                self.d_pages.truncate(row, M - 1)

        # splice the new rows into the batched state: ONE fetch, which
        # waits for the round still in flight and for these prefills
        with _span("engine.state_splice"):
            trace.incr("engine.admit_host_sync")
            pending, tpos, dpos, pnp, routing = jax.device_get(
                (state.pending, state.target_pos, state.draft_pos, prompts,
                 routing))
            if routing is not None:
                self._count_routing(routing)
            n_grow = max(0, max(r + 1 for r in rows) - B) if rows else 0
            pending = np.concatenate([np.asarray(pending),
                                      np.zeros(n_grow, pnp.dtype)])
            tpos = np.concatenate([np.asarray(tpos),
                                   np.zeros(n_grow, np.int32)])
            dpos = np.concatenate([np.asarray(dpos),
                                   np.zeros(n_grow, np.int32)])
            committed = list(state.committed) + [None] * n_grow
            for i, row in enumerate(rows):
                pending[row] = pnp[i, -1]
                tpos[row] = dpos[row] = M - 1
                committed[row] = list(pnp[i])
            new_state = StreamState(pending=jnp.asarray(pending),
                                    target_pos=jnp.asarray(tpos, jnp.int32),
                                    draft_pos=jnp.asarray(dpos, jnp.int32),
                                    committed=committed)
        return new_state, rows

    def retire_stream(self, row: int) -> None:
        """Return every page of ``row`` to the pool and recycle the batch
        slot.  The row keeps riding batched forwards frozen (writes through
        its emptied page table are dropped) until a new stream reuses it."""
        if self.cache_kind != "paged":
            raise RuntimeError("contiguous engines cannot retire streams")
        if row in self._retired:
            return
        self.t_pages.free_stream(row)
        self.d_pages.free_stream(row)
        self._retired.add(row)
        self._free_rows.append(row)
        self._free_rows.sort()

    # ------------------------------------------------------------------
    # compiled-path warmup
    # ------------------------------------------------------------------

    def warmup(self, state: StreamState, buckets, vhat: int = 64):
        """Pre-compile the jitted round steps at the given (B, L) buckets.

        Call after ``start()``.  Each bucket runs one draft + verify +
        commit step with dummy inputs at EXACTLY the shapes/dtypes the real
        dispatch uses, so serving never pays a trace+compile mid-round
        (gateway cold starts take minutes at real shapes); with
        ``setup_compilation_cache`` installed by the entry point the
        executables also persist across process restarts.  Returns
        ``(state, info)`` where ``info`` maps each bucket to its warmup
        seconds — callers MUST adopt the returned state: in ``jit+donate``
        mode the commit warmup donates the state arrays (it is a no-op
        commit: every slot skipped, values unchanged).

        Paged engines warm against the REAL pools under an all--1 page
        table — window writes are dropped, so the donated pool comes back
        bit-identical and is adopted.  Contiguous engines allocate a
        throwaway zero cache per bucket (their forwards need the cache
        batch axis to match the bucket) and only make sense at the full
        batch size.  No-op in eager mode.
        """
        if self._steps.draft is None:
            return state, {}
        paged = self.cache_kind == "paged"
        info: dict[tuple[int, int], float] = {}
        key = jax.random.PRNGKey(0)
        for n, L in sorted({(int(n), int(L)) for n, L in buckets}):
            t0 = time.perf_counter()
            pending = jnp.zeros((n,), state.pending.dtype)
            pos = jnp.zeros((n,), jnp.int32)
            if paged:
                blank_pt = jax.device_put(
                    np.full((n, self.pages_per_stream), -1, np.int32))
                d_kv, t_kv = self.d_cache, self.t_cache
            else:
                blank_pt = None
                d_kv = self.draft.init_cache(n, self.max_len,
                                             self.cache_dtype)
                t_kv = self.target.init_cache(n, self.max_len,
                                              self.cache_dtype)
            dres = self._steps.draft(self.d_params, d_kv, blank_pt, pending,
                                     pos, key, L=L, vhat=vhat)
            if paged:
                self.d_cache = dres.cache
            # chain the draft outputs into verify: exactly the real
            # shapes/dtypes with zero bookkeeping
            dlen = jax.device_put(np.full(n, L, np.int32))
            vres, t_out = self._steps.verify(
                self.t_params, t_kv, blank_pt, pending, dres.tokens,
                dres.probs, dres.q_idx, dres.q_val, pos, dlen, key)
            if paged:
                self.t_cache = t_out
            rows = jax.device_put(np.full(n, -1, np.int32))
            skip = jax.device_put(np.ones(n, bool))
            pend, tpos, dpos, emission = self._steps.commit(
                state.pending, state.target_pos, state.draft_pos, rows,
                skip, vres.output_tokens, vres.accept_counts)
            state = StreamState(pending=pend, target_pos=tpos,
                                draft_pos=dpos, committed=state.committed)
            jax.block_until_ready((pend, emission))
            info[(n, L)] = time.perf_counter() - t0
        return state, info

    # ------------------------------------------------------------------
    # round steps (reusable on row subsets — the continuous engine's core)
    # ------------------------------------------------------------------

    def _ticket_rows(self, ticket: RoundTicket) -> list:
        return (list(range(len(ticket.freeze))) if ticket.rows is None
                else ticket.rows)

    def draft_rows(self, state: StreamState, rows, lengths, key,
                   vhat: int = 64, freeze=None,
                   pad_to: int = 0) -> RoundTicket:
        """Dispatch SLM drafting for a row subset; returns a ``RoundTicket``.

        ``rows=None`` drafts the full batch (the only mode contiguous
        caches support — their forwards cannot run on row subsets).  Paged
        engines accept any subset, including ``-1`` padding entries
        (all--1 page-table rows: cache writes dropped, outputs discarded
        at commit) so a continuous driver can bucket batch shapes.

        Page mappings of live rows are extended to cover the L+1
        verification window up front, ATOMICALLY: a pool-dry failure rolls
        every grown row back and re-raises ``PagePoolExhausted``, so the
        caller can hold the streams READY and retry once in-flight commits
        return pages.  ``pad_to`` forces the dispatched window length
        (length-bucket shaping); acceptance is still capped at the true
        per-row ``lengths`` by the verifier.

        Nothing here blocks on device results: the draft forwards are
        dispatched asynchronously and the ticket only references their
        output arrays."""
        if needs_state_rollback(self.draft_cfg):
            raise NotImplementedError(
                "SSM draft models need snapshot drafting; assigned pairs use "
                "attention SLMs (DESIGN.md §Arch-applicability)")
        paged = self.cache_kind == "paged"
        full = rows is None
        if not paged and not full:
            raise RuntimeError("contiguous caches run full-batch rounds "
                               "only; row subsets need cache_kind='paged'")
        row_list = (list(range(int(state.pending.shape[0]))) if full
                    else [int(r) for r in rows])
        n = len(row_list)
        lengths = np.asarray(lengths, dtype=np.int64)
        frz = (np.zeros(n, dtype=bool) if freeze is None
               else np.asarray(freeze, dtype=bool).copy())
        for i, b in enumerate(row_list):
            if b < 0 or b in self._retired:
                frz[i] = True
        L = max(int(lengths.max()), int(pad_to))

        d_pt = None
        if paged:
            # growth is clamped at the stream ceiling (window writes past
            # max_len drop — the contiguous slab's semantics) and atomic: a
            # pool-dry failure rolls every row back so the dispatch leaves
            # the mappings untouched.  Positions come from the host-side
            # committed lists (invariant: target_pos == draft_pos ==
            # len(committed) - 1 on every path), NOT from the device arrays
            # — growing the mapping costs zero device reads.
            cap = self.pages_per_stream * self.page_size
            grown: list[tuple[int, int, int]] = []
            with _span("engine.page_alloc", {"B": n, "L": L}):
                try:
                    for i, b in enumerate(row_list):
                        if frz[i]:
                            continue
                        pos_b = len(state.committed[b]) - 1
                        grown.append((b, self.t_pages.length(b),
                                      self.d_pages.length(b)))
                        self.t_pages.extend(b, min(pos_b + L + 1, cap))
                        self.d_pages.extend(b, min(pos_b + L + 1, cap))
                except PagePoolExhausted:
                    for b, t_len, d_len in grown:
                        self.t_pages.truncate(b, t_len)
                        self.d_pages.truncate(b, d_len)
                    raise
            d_pt = self.d_pages.device_table(row_list)

        if full:
            pending, dpos, tpos = (state.pending, state.draft_pos,
                                   state.target_pos)
        else:
            idx = jax.device_put(
                np.asarray([max(b, 0) for b in row_list], np.int32))
            live = jax.device_put(np.asarray([b >= 0 for b in row_list]))
            # the zero fill is device_put EXPLICITLY: a python-scalar 0 (or
            # jnp.zeros, which embeds one) is an implicit h2d transfer and
            # trips jax.transfer_guard("disallow") on the dispatch path
            z = jax.device_put(np.zeros((), state.pending.dtype))
            zi = jax.device_put(np.zeros((), np.int32))
            pending = jnp.where(live, jnp.take(state.pending, idx), z)
            dpos = jnp.where(live, jnp.take(state.draft_pos, idx), zi)
            tpos = jnp.where(live, jnp.take(state.target_pos, idx), zi)

        # --- step 2: distributed drafting (SLM) ---
        with _span("engine.draft", {"B": n, "L": L}):
            if self._steps.draft is not None:
                # compiled path: ONE jitted call per (n, L) bucket; the
                # draft KV pytree is passed (and in jit+donate mode donated)
                # as an argument, the page table rides un-donated
                draft_res = self._steps.draft(self.d_params, self.d_cache,
                                              d_pt, pending, dpos, key,
                                              L=L, vhat=vhat)
                self.d_cache = draft_res.cache
                # the adopted cache must never be re-read through the
                # ticket: the NEXT draft call donates it
                draft_res = dataclasses.replace(draft_res, cache=None)
            else:
                d_cache = (dict(self.d_cache, pages=d_pt) if paged
                           else self.d_cache)
                draft_res = generate_drafts(self.draft, self.d_params,
                                            d_cache, pending, dpos, L, key,
                                            vhat=vhat)
                self.d_cache = strip_view(draft_res.cache)
        return RoundTicket(rows=None if full else row_list, lengths=lengths,
                           L=L, freeze=frz, pending=pending, target_pos=tpos,
                           draft=draft_res)

    def verify_rows(self, ticket: RoundTicket, key) -> RoundTicket:
        """Dispatch the batched target pass + exact accept/reject for a
        drafted ticket.  Asynchronous like ``draft_rows``: the returned
        ticket's ``res`` arrays are in flight; ``commit_rows`` is the only
        host sync point, so drafting for other streams can be dispatched
        while this verification runs on device."""
        paged = self.cache_kind == "paged"
        row_list = self._ticket_rows(ticket)
        n = len(row_list)
        draft_res = ticket.draft
        t_pt = self.t_pages.device_table(row_list) if paged else None
        draft_len = jax.device_put(np.asarray(ticket.lengths, np.int32))

        if self._steps.verify is not None:
            # compiled path: the target pass AND the accept/reject run in
            # one jitted call (per (n, L) bucket); the target KV pytree is
            # donated in jit+donate mode, the page table rides un-donated
            with _span("engine.target_pass",
                       {"B": n, "W": ticket.L + 1}):
                res, t_kv = self._steps.verify(
                    self.t_params, self.t_cache, t_pt, ticket.pending,
                    draft_res.tokens, draft_res.probs, draft_res.q_idx,
                    draft_res.q_val, ticket.target_pos, draft_len, key)
                self.t_cache = t_kv
            ticket.res = res
            return ticket

        t_cache = dict(self.t_cache, pages=t_pt) if paged else self.t_cache

        # --- step 4: batched verification (LLM) ---
        window = jnp.concatenate([ticket.pending[:, None], draft_res.tokens],
                                 axis=1)                       # (n, L+1)
        with _span("engine.target_pass", {"B": n, "W": ticket.L + 1}):
            if needs_state_rollback(self.target_cfg):
                logits, t_cache, snaps = self.target.forward_window(
                    self.t_params, window, t_cache, ticket.target_pos,
                    return_snapshots=True)
            elif self.routed:
                logits, t_cache, routing = self.target.forward_window(
                    self.t_params, window, t_cache, ticket.target_pos,
                    return_aux=True)
                snaps = None
            else:
                logits, t_cache = self.target.forward_window(
                    self.t_params, window, t_cache, ticket.target_pos)
                snaps = None

        with _span("engine.verify_tokens", {"B": n, "L": ticket.L}):
            res = verify_drafts(key, draft_res.tokens, draft_res.probs,
                                logits, q_idx=draft_res.q_idx,
                                q_val=draft_res.q_val, draft_len=draft_len)
            if self.routed:
                res = dataclasses.replace(res, routing=routing)

        # target cache: row i processed [pending, d_1..d_n]; snapshot index
        # n (0-based: snapshot t is the state after feeding window[:, :t+1])
        if snaps is not None:
            sel = select_snapshots(snaps, res.accept_counts,
                                   self.target.CACHE_BATCH_AXES)
            t_cache = merge_snapshot_into_cache(t_cache, sel)
        self.t_cache = strip_view(t_cache)
        ticket.res = res
        return ticket

    def commit_rows(self, state: StreamState, ticket: RoundTicket,
                    skip=None):
        """Land a verified ticket — THE host sync point of a round.

        The state arrays stay device-resident: a (jitted) ``commit_step``
        scatter-updates ONLY the ticket's rows of pending/target_pos/
        draft_pos on device (in ``jit+donate`` mode the old buffers are
        donated into the new ones), and the single blocking fetch of the
        round is the packed ``(n, L+2)`` emission — ``[advance, tokens...]``
        per slot — that extends the host-side committed lists and drives
        the page-pool truncation.  ``skip`` (aligned with the ticket's
        rows) marks members that must NOT commit — streams retired while
        the batch was in flight; rows retired through the engine and ``-1``
        padding rows are skipped automatically, so a mid-verify disconnect
        never corrupts the rest of the batch.  Returns ``(new_state,
        accepted)``: accepted counts incl. the bonus token, 0 for
        skipped/frozen rows, aligned with the ticket."""
        paged = self.cache_kind == "paged"
        row_list = self._ticket_rows(ticket)
        n = len(row_list)
        res = ticket.res
        skip_np = (np.zeros(n, dtype=bool) if skip is None
                   else np.asarray(skip, dtype=bool).copy())
        skip_np |= ticket.freeze
        for i, b in enumerate(row_list):
            if b < 0 or b in self._retired:
                skip_np[i] = True
        with _span("engine.commit", {"B": n}):
            rows_dev = jax.device_put(np.asarray(row_list, np.int32))
            skip_dev = jax.device_put(skip_np)
            pend, tpos, dpos, emission = self._steps.commit(
                state.pending, state.target_pos, state.draft_pos, rows_dev,
                skip_dev, res.output_tokens, res.accept_counts)
            if res.routing is None:
                pack = self._host_fetch(emission)    # the ONE host sync
            else:
                pack, routing = self._host_fetch((emission, res.routing))
                self._count_routing(routing)
            accepted = pack[:, 0].astype(np.int64)
            with _span("engine.page_free", {"B": n}):
                for i, b in enumerate(row_list):
                    adv = int(pack[i, 0])
                    if adv == 0:
                        continue
                    state.committed[b].extend(pack[i, 1:1 + adv].tolist())
                    if paged:
                        # speculative rejection hands pages straight back
                        new_len = len(state.committed[b]) - 1
                        self.t_pages.truncate(b, new_len)
                        self.d_pages.truncate(b, new_len)
        self.last_accepted = accepted if ticket.rows is None else None
        new_state = StreamState(pending=pend, target_pos=tpos,
                                draft_pos=dpos, committed=state.committed)
        return new_state, accepted

    def spin_round(self, state: StreamState, lengths: np.ndarray,
                   key: jax.Array, vhat: int = 64,
                   freeze: np.ndarray | None = None, draft_width: int = 1,
                   tree: bool | None = None):
        """One Multi-SPIN round with per-stream draft lengths (zero-padded to
        the max).  Returns (state, VerifyResult, draft_result).

        ``draft_width`` J > 1 runs the TOKEN-TREE round instead (the
        ``multidraft`` scheme): J drafts per stream packed into a
        prefix-deduplicated tree, scored in ONE ancestor-masked target pass,
        with the longest accepted root-to-leaf path committed
        (``_spin_round_tree``).  ``tree=True`` forces the tree machinery at
        J = 1 — it commits bit-identical tokens to the sequential path
        (equivalence-tested), so this is only useful for testing.

        ``freeze`` marks streams that must NOT advance this round (retired
        requests, or the off half of a pipelined schedule).  Frozen rows
        still ride through the batched forwards (the reference engine cannot
        skip batch rows) but commit nothing: positions, pending token, and
        committed text are untouched.  For attention targets/drafts the
        cache is pointer-indexed, so the stale window writes are overwritten
        on the row's next live round; SSM targets would need a pre-window
        state restore and are rejected.  Paged engines additionally freeze
        retired rows and grow/shrink page mappings around the round: live
        rows extend to cover the L+1 window up front and hand back every
        page past the accepted prefix afterwards.
        """
        if tree is None:
            tree = draft_width > 1
        if tree:
            return self._spin_round_tree(state, lengths, key, vhat=vhat,
                                         freeze=freeze, J=int(draft_width))
        B = state.pending.shape[0]
        frz_np = (np.zeros(B, dtype=bool) if freeze is None
                  else np.asarray(freeze, dtype=bool).copy())
        if self._retired:
            frz_np[list(self._retired)] = True
        if frz_np.any() and needs_state_rollback(self.target_cfg):
            raise NotImplementedError(
                "freezing streams of an SSM/hybrid target needs a pre-window "
                "state snapshot (see ROADMAP open items)")
        k_draft, k_verify = jax.random.split(key)
        # the lockstep round IS the three continuous steps on the full batch
        # (same dispatch shapes and key discipline -> bit-identical tokens)
        ticket = self.draft_rows(state, None, lengths, k_draft, vhat=vhat,
                                 freeze=frz_np)
        ticket = self.verify_rows(ticket, k_verify)
        new_state, _ = self.commit_rows(state, ticket)
        return new_state, ticket.res, ticket.draft

    # ------------------------------------------------------------------
    # token-tree multi-draft round (SpecInfer-style verification)
    # ------------------------------------------------------------------

    def _spin_round_tree(self, state: StreamState, lengths: np.ndarray,
                         key: jax.Array, vhat: int,
                         freeze: np.ndarray | None, J: int):
        """One multi-draft round: J drafts per stream, packed into a
        prefix-deduplicated token tree, scored in ONE ancestor-masked target
        pass, longest accepted root-to-leaf path committed.

        Cache discipline: the W+1 tree window (W = J * L) occupies target
        SLOTS [pos, pos + W] in construction order while each node keeps its
        tree DEPTH as rope position; after acceptance the winning branch's
        K/V are SCATTERED from their tree-window slots (target) and the
        winning run's window snapshot (draft) into the committed slots —
        ``tree_commit="repair"`` instead re-forwards [pending, accepted
        path] through both models (the pre-scatter reference path; committed
        tokens are identical either way, they are decided before the cache
        fix-up).  Paged engines hand every page past the accepted prefix
        (all dead branches) back to the pool.  At J = 1 the tree is a
        chain, the window IS the sequential window, and no fix-up runs:
        tokens and caches are bit-identical to ``spin_round``.
        """
        if self.target_cfg.attention == "mla":
            raise NotImplementedError(_NO_MLA_TREE)
        for role, cfg in (("target", self.target_cfg),
                          ("draft", self.draft_cfg)):
            # DecoderLM families only: the ancestor-masked window needs
            # pointer-rollback attention caches AND the forward_window
            # (window_mask=, window_depth=) signature — SSM/hybrid state
            # cannot be pointer-rolled, enc-dec lacks the masked window
            if cfg.family not in ("dense", "moe", "vlm"):
                raise NotImplementedError(
                    f"tree verification needs an attention decoder "
                    f"({role} family {cfg.family!r}): divergent branches "
                    f"commit by pointer rollback and one ancestor-masked "
                    f"window pass (see ROADMAP open items)")
        B = state.pending.shape[0]
        lengths = np.asarray(lengths, dtype=np.int64)
        frz_np = (np.zeros(B, dtype=bool) if freeze is None
                  else np.asarray(freeze, dtype=bool).copy())
        if self._retired:
            frz_np[list(self._retired)] = True
        L = int(lengths.max())
        W = J * L
        k_draft, k_verify = jax.random.split(key)

        paged = self.cache_kind == "paged"
        if paged:
            # the TARGET maps the whole W+1 tree window up front; the draft
            # side only ever holds one run (L+1) — repair fits under both.
            # Positions come from the host-side committed lists (target_pos
            # == draft_pos == len(committed) - 1), zero device reads.
            cap = self.pages_per_stream * self.page_size
            grown: list[tuple[int, int, int]] = []
            with _span("engine.page_alloc", {"B": B, "W": W}):
                try:
                    for b in range(B):
                        if frz_np[b]:
                            continue
                        pos_b = len(state.committed[b]) - 1
                        grown.append((b, self.t_pages.length(b),
                                      self.d_pages.length(b)))
                        self.t_pages.extend(b, min(pos_b + W + 1, cap))
                        self.d_pages.extend(b, min(pos_b + L + 1, cap))
                except PagePoolExhausted:
                    for b, t_len, d_len in grown:
                        self.t_pages.truncate(b, t_len)
                        self.d_pages.truncate(b, d_len)
                    raise
            t_pt = self.t_pages.device_table(range(B))
            d_pt = self.d_pages.device_table(range(B))
        else:
            t_pt = d_pt = None
        # compiled path: the forest, the target pass + accept test and the
        # scatter-commit each run as ONE jitted call per (B, J, L) bucket
        # with the KV pools passed (and in jit+donate mode donated) as
        # arguments — the caches are adopted right after each call
        steps = self._steps if self._steps.forest is not None else None
        view = lambda kv, pt: kv if pt is None else dict(kv, pages=pt)

        # --- step 2: J drafting runs per stream (SLM) ---
        scatter = self.tree_commit == "scatter" and J > 1
        with _span("engine.draft_forest", {"B": B, "L": L, "J": J}):
            if steps is not None:
                forest = steps.forest(self.d_params, self.d_cache, d_pt,
                                      state.pending, state.draft_pos,
                                      k_draft, L=L, J=J, vhat=vhat,
                                      keep_windows=scatter)
                self.d_cache = forest.cache
                forest = dataclasses.replace(forest, cache=None)
            else:
                forest = generate_draft_forest(
                    self.draft, self.d_params, view(self.d_cache, d_pt),
                    state.pending, state.draft_pos, L, J, k_draft,
                    vhat=vhat, keep_windows=scatter)
                self.d_cache = strip_view(forest.cache)

        # --- pack into the prefix-deduplicated tree (host-side) ---
        with _span("engine.tree_build", {"B": B, "L": L, "J": J}):
            # ONE batched fetch for everything the host-side trie build
            # needs, into (J, L)-bucketed scratch buffers reused across
            # rounds instead of 8 fresh allocations per call
            tok_np, p_np, qi_np, qv_np, pend_np = self._host_fetch(
                (forest.tokens, forest.probs, forest.q_idx, forest.q_val,
                 state.pending))
            ttree = build_token_tree(tok_np, p_np, qi_np, qv_np, lengths,
                                     scratch=self._tree_scratch)
            window = jax.device_put(
                ttree.window_tokens(pend_np).astype(np.int32))  # (B, W+1)
            wmask = jax.device_put(ttree.window_mask())
            wdepth = jax.device_put(ttree.window_depth().astype(np.int32))

        tree_args = tuple(jnp.asarray(a) for a in (
            ttree.tokens, ttree.parents, ttree.depth, ttree.probs,
            ttree.paths))
        q_args = (jnp.asarray(ttree.q_idx), jnp.asarray(ttree.q_val),
                  jnp.asarray(lengths, jnp.int32))

        # --- step 4: ONE ancestor-masked target pass over the whole tree ---
        if steps is not None:
            with _span("engine.target_pass",
                       {"B": B, "W": W + 1, "J": J}):
                res, self.t_cache = steps.tree_verify(
                    self.t_params, self.t_cache, t_pt, window, wmask, wdepth,
                    state.target_pos, k_verify, *tree_args, *q_args)
        else:
            with _span("engine.target_pass",
                       {"B": B, "W": W + 1, "J": J}):
                logits, t_cache = self.target.forward_window(
                    self.t_params, window, view(self.t_cache, t_pt),
                    state.target_pos, window_mask=wmask, window_depth=wdepth)
                self.t_cache = strip_view(t_cache)
            with _span("engine.verify_tokens",
                       {"B": B, "L": L, "J": J}):
                res = verify_tree(k_verify, *tree_args, logits, *q_args)

        # --- step 5a: land the accepted path's K/V (a J=1 chain already IS
        # the sequential window: nothing to move)
        frz = jnp.asarray(frz_np)
        if scatter:
            # scatter-commit: the ancestor-masked target pass ALREADY
            # computed the accepted path's K/V (each tree node conditions on
            # exactly its root-to-node path), so move the winning branch's
            # rows from their tree-window slots into the committed slots —
            # no repair forward, and no host sync on accept_counts
            with _span("engine.kv_commit", {"B": B, "L": L, "J": J}):
                if steps is not None:
                    self.t_cache, self.d_cache = steps.tree_commit(
                        self.t_cache, t_pt, self.d_cache, d_pt,
                        forest.windows, tree_args[4], res.winner,
                        res.accept_counts, frz, state.target_pos,
                        state.draft_pos)
                else:
                    t_cache, d_cache = tree_scatter_commit(
                        view(self.t_cache, t_pt), view(self.d_cache, d_pt),
                        forest.windows, tree_args[4], res.winner,
                        res.accept_counts, frz, state.target_pos,
                        state.draft_pos)
                    self.t_cache = strip_view(t_cache)
                    self.d_cache = strip_view(d_cache)
        elif J > 1:
            # repair forward (kept as the reference path, and for targets
            # whose window pass cannot donate K/V): one plain causal window
            # over [pending, accepted path] rewrites the surviving slots
            n_max = int(self._host_fetch(res.accept_counts).max())
            repair = jnp.concatenate(
                [state.pending[:, None], res.output_tokens[:, :n_max]],
                axis=1)                                        # (B, n_max+1)
            with _span("engine.cache_repair", {"B": B, "n": n_max + 1}):
                _, t_cache = self.target.forward_window(
                    self.t_params, repair, view(self.t_cache, t_pt),
                    state.target_pos)
                _, d_cache = self.draft.forward_window(
                    self.d_params, repair, view(self.d_cache, d_pt),
                    state.draft_pos)
                self.t_cache = strip_view(t_cache)
                self.d_cache = strip_view(d_cache)

        # --- step 5b: commit + rollback (identical to the sequential round)
        adv = jnp.where(frz, 0, 1 + res.accept_counts)
        new_target_pos = state.target_pos + adv
        new_draft_pos = state.draft_pos + adv
        sampled = jnp.take_along_axis(
            res.output_tokens, res.accept_counts[:, None], axis=1)[:, 0]
        new_pending = jnp.where(frz, state.pending, sampled)

        # one batched fetch lands the commit on the host (tokens + counts;
        # positions for the page truncation ride along for free)
        out_np, n_np, ntp, ndp = self._host_fetch(
            (res.output_tokens, res.accept_counts, new_target_pos,
             new_draft_pos))
        self.last_accepted = np.where(frz_np, 0, n_np + 1).astype(np.int64)
        for b in range(B):
            if not frz_np[b]:
                state.committed[b].extend(out_np[b, :n_np[b] + 1].tolist())

        if paged:
            # every page past the accepted prefix — all dead branches of the
            # tree — returns to the pool here
            with _span("engine.page_free", {"B": B}):
                for b in range(B):
                    if not frz_np[b]:
                        self.t_pages.truncate(b, int(ntp[b]))
                        self.d_pages.truncate(b, int(ndp[b]))

        new_state = StreamState(pending=new_pending, target_pos=new_target_pos,
                                draft_pos=new_draft_pos,
                                committed=state.committed)
        return new_state, res, forest
