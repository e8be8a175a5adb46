"""Paged KV-cache subsystem: allocator invariants, paged-vs-contiguous
engine parity, and the EngineBackend churn lifecycle (dynamic admission)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.api import (  # noqa: E402
    CellConfig,
    EngineBackend,
    MultiSpinCell,
    PagedKVCache,
    PagePoolExhausted,
    Request,
)
from repro.configs import get_config  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving import SpecEngine  # noqa: E402


# ---------------------------------------------------------------------------
# Allocator property tests
# ---------------------------------------------------------------------------

def test_allocator_basic_lifecycle():
    mgr = PagedKVCache(num_pages=10, page_size=4, pages_per_stream=4)
    mgr.alloc_stream(0, 7)                     # 2 pages
    assert mgr.num_free_pages == 8
    mgr.extend(0, 13)                          # 4 pages total
    assert mgr.num_free_pages == 6
    assert mgr.length(0) == 13
    freed = mgr.truncate(0, 5)                 # back to 2 pages
    assert freed == 2 and mgr.num_free_pages == 8
    assert mgr.free_stream(0) == 2
    assert mgr.num_free_pages == 10
    mgr.check_invariants()


def test_allocator_rejects_over_capacity():
    mgr = PagedKVCache(num_pages=4, page_size=4, pages_per_stream=4)
    assert mgr.can_allocate(16)
    assert not mgr.can_allocate(17)            # > pages_per_stream
    mgr.alloc_stream(0, 12)                    # 3 of 4 pages
    assert mgr.can_allocate(4)
    assert not mgr.can_allocate(5)
    with pytest.raises(PagePoolExhausted):
        mgr.alloc_stream(1, 8)
    # failed allocation must not leak partial state
    mgr.check_invariants()
    assert mgr.num_free_pages == 1
    assert 1 not in mgr.streams()
    with pytest.raises(PagePoolExhausted):
        mgr.extend(0, 17)                      # past pages_per_stream
    mgr.check_invariants()


def test_allocator_double_ops_raise():
    mgr = PagedKVCache(num_pages=8, page_size=2, pages_per_stream=4)
    mgr.alloc_stream(3, 4)
    with pytest.raises(ValueError):
        mgr.alloc_stream(3, 2)                 # double alloc
    mgr.free_stream(3)
    with pytest.raises(KeyError):
        mgr.free_stream(3)                     # double free
    mgr.check_invariants()


def test_allocator_random_sequences_never_leak():
    """Random alloc/extend/truncate/free churn: after every operation the
    pool partitions exactly into free + mapped pages (no leak, no double
    mapping)."""
    rng = np.random.default_rng(0)
    for trial in range(20):
        mgr = PagedKVCache(num_pages=int(rng.integers(4, 40)),
                           page_size=int(rng.integers(1, 8)),
                           pages_per_stream=int(rng.integers(2, 10)))
        live: dict[int, int] = {}
        next_sid = 0
        for _ in range(200):
            op = rng.integers(4)
            if op == 0:
                length = int(rng.integers(0, mgr.pages_per_stream
                                          * mgr.page_size + 2))
                try:
                    mgr.alloc_stream(next_sid, length)
                    live[next_sid] = length
                except PagePoolExhausted:
                    assert not mgr.can_allocate(length)
                next_sid += 1
            elif op == 1 and live:
                sid = int(rng.choice(list(live)))
                new_len = live[sid] + int(rng.integers(0, 12))
                try:
                    mgr.extend(sid, new_len)
                    live[sid] = new_len
                except PagePoolExhausted:
                    pass
            elif op == 2 and live:
                sid = int(rng.choice(list(live)))
                live[sid] = int(rng.integers(0, live[sid] + 1))
                mgr.truncate(sid, live[sid])
            elif op == 3 and live:
                sid = int(rng.choice(list(live)))
                mgr.free_stream(sid)
                del live[sid]
            mgr.check_invariants()
        used = sum(mgr.pages_for(length) for length in live.values())
        assert mgr.num_allocated_pages == used


# ---------------------------------------------------------------------------
# Paged model forward == contiguous model forward
# ---------------------------------------------------------------------------

def test_paged_forward_window_matches_contiguous():
    cfg = get_config("qwen2.5-3b").smoke()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, M, L, max_len, ps = 3, 10, 4, 64, 16
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, M), 0,
                                 cfg.vocab_size)

    cache = m.init_cache(B, max_len, jnp.float32)
    lg_c, cache, _ = m.prefill(params, prompts[:, :-1], cache)

    mgr = PagedKVCache(num_pages=16, page_size=ps,
                       pages_per_stream=max_len // ps)
    pool = m.init_paged_cache(16, ps, jnp.float32)
    for b in range(B):
        mgr.alloc_stream(b, M - 1)
    pool = dict(pool, pages=jnp.asarray(mgr.page_table(range(B))))
    lg_p, pool, _ = m.prefill(params, prompts[:, :-1], pool)
    np.testing.assert_allclose(np.asarray(lg_c), np.asarray(lg_p),
                               rtol=1e-5, atol=1e-5)

    # two windows at increasingly ragged offsets (accept/reject divergence)
    pos = jnp.full((B,), M - 1, jnp.int32)
    for step, deltas in enumerate([(2, 5, 1), (4, 1, 3)]):
        win = jax.random.randint(jax.random.PRNGKey(2 + step), (B, L + 1),
                                 0, cfg.vocab_size)
        for b in range(B):
            mgr.extend(b, int(pos[b]) + L + 1)
        pool["pages"] = jnp.asarray(mgr.page_table(range(B)))
        o_c, cache = m.forward_window(params, win, cache, pos)
        o_p, pool = m.forward_window(params, win, pool, pos)
        np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_p),
                                   rtol=1e-5, atol=1e-5)
        pos = pos + jnp.asarray(deltas)
        for b in range(B):
            mgr.truncate(b, int(pos[b]))         # rejected pages return
        mgr.check_invariants()


# ---------------------------------------------------------------------------
# Seeded engine parity: identical committed tokens + accept counts
# ---------------------------------------------------------------------------

def _engine_pair(max_len=96):
    tcfg = get_config("qwen2.5-3b").smoke()
    dcfg = tcfg.replace(num_layers=1, d_model=32, num_heads=2, num_kv_heads=1,
                        head_dim=16, d_ff=64, name="draft-smoke")
    return tcfg, dcfg


def test_paged_engine_matches_contiguous_engine():
    tcfg, dcfg = _engine_pair()
    lengths = np.array([3, 5, 2])
    results = {}
    for kind in ("contiguous", "paged"):
        eng = SpecEngine(tcfg, dcfg, max_len=96, cache_kind=kind)
        eng.init_params(jax.random.PRNGKey(0))
        prompts = jax.random.randint(jax.random.PRNGKey(1), (3, 10), 0,
                                     tcfg.vocab_size)
        state = eng.start(prompts)
        counts = []
        for r in range(3):
            state, res, _ = eng.spin_round(state, lengths,
                                           jax.random.PRNGKey(10 + r))
            counts.append(np.asarray(res.accept_counts))
        results[kind] = (state.committed, np.stack(counts),
                         np.asarray(state.target_pos))
    c_com, c_cnt, c_pos = results["contiguous"]
    p_com, p_cnt, p_pos = results["paged"]
    np.testing.assert_array_equal(c_cnt, p_cnt)
    np.testing.assert_array_equal(c_pos, p_pos)
    assert c_com == p_com


def test_paged_engine_incremental_consistency_after_churn():
    """After retire + rejoin + batch growth, every live stream's incremental
    logits must equal a from-scratch re-scoring of its committed text."""
    tcfg, dcfg = _engine_pair()
    eng = SpecEngine(tcfg, dcfg, max_len=96, cache_kind="paged")
    eng.init_params(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (3, 10), 0,
                                 tcfg.vocab_size)
    state = eng.start(prompts)
    for r in range(2):
        state, _, _ = eng.spin_round(state, np.array([3, 4, 2]),
                                     jax.random.PRNGKey(10 + r))
    eng.retire_stream(1)
    state, rows = eng.add_streams(
        state, jax.random.randint(jax.random.PRNGKey(7), (1, 8), 0,
                                  tcfg.vocab_size))
    assert rows == [1]                      # retired row recycled
    state, rows2 = eng.add_streams(
        state, jax.random.randint(jax.random.PRNGKey(8), (1, 8), 0,
                                  tcfg.vocab_size))
    assert rows2 == [3]                     # batch grows past start size
    for r in range(2):
        state, _, _ = eng.spin_round(state, np.array([2, 3, 2, 2]),
                                     jax.random.PRNGKey(50 + r))
    eng.t_pages.check_invariants()
    eng.d_pages.check_invariants()

    B = state.pending.shape[0]
    # a real decode step extends the mapping before writing its token (the
    # pending position may start a fresh page)
    for b in range(B):
        eng.t_pages.extend(b, int(state.target_pos[b]) + 1)
    view = dict(eng.t_cache,
                pages=jnp.asarray(eng.t_pages.page_table(range(B))))
    inc, _ = eng.target.forward_window(eng.t_params, state.pending[:, None],
                                       view, state.target_pos)
    for b in range(B):
        assert state.committed[b][-1] == int(state.pending[b])
        seq = jnp.asarray(state.committed[b])[None, :]
        full, _ = eng.target.apply(eng.t_params, seq)
        np.testing.assert_allclose(np.asarray(inc[b, 0]),
                                   np.asarray(full[0, -1]),
                                   rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# EngineBackend churn lifecycle through the cell
# ---------------------------------------------------------------------------

def test_engine_backend_churn_lifecycle():
    """The acceptance scenario: a request submitted AFTER engine.start() is
    admitted (no 'engine batch exhausted'), completes, departs return their
    pages, and a later request recycles the row."""
    tcfg, dcfg = _engine_pair()
    eng = SpecEngine(tcfg, dcfg, max_len=128, cache_kind="paged")
    eng.init_params(jax.random.PRNGKey(0))
    K, M = 2, 6
    prompts = jax.random.randint(jax.random.PRNGKey(1), (K, M), 0,
                                 tcfg.vocab_size)
    backend = EngineBackend(eng, eng.start(prompts))
    cell = MultiSpinCell(CellConfig(scheme="fixed", L_fixed=3, max_batch=3,
                                    seed=0), backend=backend)
    for i in range(K):
        cell.submit(Request(rid=i, prompt_len=M, max_new_tokens=10,
                            alpha=0.8, T_S=0.01))
    cell.step()
    # join after start()
    cell.submit(Request(rid=99, prompt_len=6, max_new_tokens=6, alpha=0.8,
                        T_S=0.01))
    rec = cell.step()
    assert 99 in set(rec.rids.tolist())
    # leave mid-flight; the pages come back and the row is recyclable
    cell.leave(0)
    free_before = eng.t_pages.num_free_pages
    cell.submit(Request(rid=100, prompt_len=6, max_new_tokens=6, alpha=0.8,
                        T_S=0.01))
    rec = cell.step()
    assert set(rec.rids.tolist()) >= {1, 100}
    assert eng.t_pages.num_free_pages < free_before   # rejoin took pages
    cell.drain()
    assert cell.scheduler.stats.completed == 3        # 1, 99, 100 (0 left)
    eng.t_pages.check_invariants()
    eng.d_pages.check_invariants()
    assert eng.t_pages.num_allocated_pages == 0       # all pages reclaimed


def test_engine_backend_admission_blocks_on_pool_oom():
    """With a pool sized for ~1 stream, the second request must WAIT in the
    queue (admission control) instead of crashing the engine, and be
    admitted once the first stream retires."""
    tcfg, dcfg = _engine_pair()
    # ps=16: the start stream holds 1 page; admitting rid=1 would need
    # pages_for(6 + 32 headroom) = 3 > the 2 left in the pool
    eng = SpecEngine(tcfg, dcfg, max_len=64, cache_kind="paged", num_pages=3)
    eng.init_params(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0,
                                 tcfg.vocab_size)
    backend = EngineBackend(eng, eng.start(prompts), admit_headroom=32)
    cell = MultiSpinCell(CellConfig(scheme="fixed", L_fixed=2, max_batch=4,
                                    seed=0), backend=backend)
    cell.submit(Request(rid=0, prompt_len=6, max_new_tokens=6, alpha=0.8,
                        T_S=0.01))
    cell.submit(Request(rid=1, prompt_len=6, max_new_tokens=4, alpha=0.8,
                        T_S=0.01))
    rec = cell.step()
    assert rec.rids.tolist() == [0]          # rid=1 blocked by the pool
    assert len(cell.scheduler.queue) == 1
    cell.drain()                             # 0 retires -> 1 admitted
    assert cell.scheduler.stats.completed == 2
    assert eng.t_pages.num_allocated_pages == 0


def test_unservable_request_rejected_instead_of_wedging_queue():
    """A prompt that can NEVER fit a stream (> max_len) must be evicted with
    done=True — not silently block FIFO admission for everyone behind it."""
    tcfg, dcfg = _engine_pair()
    eng = SpecEngine(tcfg, dcfg, max_len=64, cache_kind="paged")
    eng.init_params(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0,
                                 tcfg.vocab_size)
    backend = EngineBackend(eng, eng.start(prompts))
    cell = MultiSpinCell(CellConfig(scheme="fixed", L_fixed=2, max_batch=3,
                                    seed=0), backend=backend)
    cell.submit(Request(rid=0, prompt_len=6, max_new_tokens=4, alpha=0.8,
                        T_S=0.01))
    cell.submit(Request(rid=1, prompt_len=200, max_new_tokens=4, alpha=0.8,
                        T_S=0.01))                 # can never fit max_len=64
    cell.submit(Request(rid=2, prompt_len=6, max_new_tokens=4, alpha=0.8,
                        T_S=0.01))
    rec = cell.step()
    assert [r.rid for r in cell.rejected] == [1]
    assert cell.rejected[0].done
    assert set(rec.rids.tolist()) == {0, 2}        # rid=2 was not blocked
    cell.drain()
    assert cell.scheduler.stats.completed == 2
    assert cell.idle


def test_contiguous_backend_still_raises_on_exhaustion():
    tcfg, dcfg = _engine_pair()
    eng = SpecEngine(tcfg, dcfg, max_len=64)
    eng.init_params(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0,
                                 tcfg.vocab_size)
    backend = EngineBackend(eng, eng.start(prompts))
    r0 = Request(rid=0, prompt_len=6, max_new_tokens=6, alpha=0.8, T_S=0.01)
    r1 = Request(rid=1, prompt_len=6, max_new_tokens=6, alpha=0.8, T_S=0.01)
    assert backend.can_admit(r0) and backend.servable(r0)
    backend.bind([r0])
    assert not backend.can_admit(r1)         # admission control says no...
    assert not backend.servable(r1)          # ...and it can never be served
    with pytest.raises(ValueError, match="batch exhausted"):
        backend.bind([r1])                   # force-binding still raises


def test_contiguous_overbatch_request_rejected_not_starved():
    """drain() must not return with requests silently parked forever: a
    request a contiguous engine can never serve is rejected explicitly."""
    tcfg, dcfg = _engine_pair()
    eng = SpecEngine(tcfg, dcfg, max_len=64)
    eng.init_params(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                                 tcfg.vocab_size)
    backend = EngineBackend(eng, eng.start(prompts))
    cell = MultiSpinCell(CellConfig(scheme="fixed", L_fixed=2, max_batch=3,
                                    seed=0), backend=backend)
    for i in range(3):                       # one more than the start batch
        cell.submit(Request(rid=i, prompt_len=6, max_new_tokens=4,
                            alpha=0.8, T_S=0.01))
    cell.drain()
    assert cell.scheduler.stats.completed == 2
    assert [r.rid for r in cell.rejected] == [2]
    assert cell.rejected[0].done
    assert cell.idle                         # nothing parked in the queue


# ---------------------------------------------------------------------------
# Paged-prefill prompt bucketing (bounded XLA trace count under churn)
# ---------------------------------------------------------------------------

def test_paged_prefill_buckets_prompt_shapes():
    """Heavy churn with many distinct prompt lengths must compile a bounded
    number of prefill traces: ``add_streams`` pads each prompt batch to its
    power-of-two bucket, and the compile-counting hook sees only bucket
    shapes.  Committed text, positions, and page accounting stay exact."""
    tcfg, dcfg = _engine_pair()
    eng = SpecEngine(tcfg, dcfg, max_len=96, cache_kind="paged",
                     num_pages=240)
    eng.init_params(jax.random.PRNGKey(0))
    traces = []
    eng.on_prefill_trace = traces.append
    state = eng.start(jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                         tcfg.vocab_size))
    prompt_lens = list(range(9, 21))            # 12 distinct lengths
    rows_of = {}
    for i, M in enumerate(prompt_lens):
        p = jax.random.randint(jax.random.PRNGKey(100 + i), (1, M), 0,
                               tcfg.vocab_size)
        state, rows = eng.add_streams(state, p)
        rows_of[rows[0]] = np.asarray(p[0])
    # one trace per BUCKET, not per distinct (n, M): lengths 9..16 -> 16,
    # 17..20 -> 32, plus the start batch's 8
    assert len(set(traces)) <= 3, traces
    assert set(traces) == set(eng.prefill_shapes)
    assert all(shape[1] in (8, 16, 32) for shape in traces)
    for row, p in rows_of.items():
        # true prompt preserved (no pad tokens leak into committed text)
        assert state.committed[row] == list(p)
        assert int(state.target_pos[row]) == len(p) - 1
        # bucket-padding pages were handed back right after the prefill
        assert eng.t_pages.length(row) == len(p) - 1
    eng.t_pages.check_invariants()
    eng.d_pages.check_invariants()
    # the padded prefill is never attended: a spin round over every stream
    # commits L+1-bounded tokens and keeps the allocator consistent
    B = state.pending.shape[0]
    state, res, _ = eng.spin_round(state, np.full(B, 3), jax.random.PRNGKey(9))
    assert np.all(np.asarray(res.output_len) <= 4)
    eng.t_pages.check_invariants()


def test_bucketed_prefill_numerics_match_exact_prefill():
    """A stream admitted through the bucketed prefill must score its
    committed text identically to the model's from-scratch forward (the pad
    K/V past the true prompt is never attended)."""
    tcfg, dcfg = _engine_pair()
    eng = SpecEngine(tcfg, dcfg, max_len=96, cache_kind="paged")
    eng.init_params(jax.random.PRNGKey(0))
    state = eng.start(jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                         tcfg.vocab_size))
    # length 11 -> bucketed to 16 (5 pad positions written, then truncated)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 11), 0,
                                tcfg.vocab_size)
    state, rows = eng.add_streams(state, prompt)
    assert (1, 16) in eng.prefill_shapes
    for r in range(2):
        state, _, _ = eng.spin_round(state, np.array([2, 3]),
                                     jax.random.PRNGKey(30 + r))
    b = rows[0]
    eng.t_pages.extend(b, int(state.target_pos[b]) + 1)
    view = dict(eng.t_cache,
                pages=jnp.asarray(eng.t_pages.page_table(range(2))))
    inc, _ = eng.target.forward_window(eng.t_params, state.pending[:, None],
                                       view, state.target_pos)
    seq = jnp.asarray(state.committed[b])[None, :]
    full, _ = eng.target.apply(eng.t_params, seq)
    np.testing.assert_allclose(np.asarray(inc[b, 0]), np.asarray(full[0, -1]),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Compiled admission prefill (one jitted step per prompt bucket)
# ---------------------------------------------------------------------------

_ADMIT_LENGTHS = (11, 20, 9)            # buckets 16, 32, 16
_ADMITTED = {}


def _admit_and_serve(mode, J):
    """start() a paged engine, admit prompts at bucketed lengths, run a few
    rounds of draft width ``J``; returns (engine, state)."""
    key = (mode, J)
    if key not in _ADMITTED:
        tcfg, dcfg = _engine_pair()
        eng = SpecEngine(tcfg, dcfg, max_len=96, cache_kind="paged",
                         num_pages=240, compile_mode=mode)
        eng.init_params(jax.random.PRNGKey(0))
        state = eng.start(jax.random.randint(jax.random.PRNGKey(1), (2, 10),
                                             0, tcfg.vocab_size))
        for i, M in enumerate(_ADMIT_LENGTHS):
            state, _ = eng.add_streams(state, jax.random.randint(
                jax.random.PRNGKey(100 + i), (1, M), 0, tcfg.vocab_size))
        B = state.pending.shape[0]
        for r in range(2):
            state, _, _ = eng.spin_round(state, np.full(B, 3),
                                         jax.random.PRNGKey(10 + r),
                                         draft_width=J)
        _ADMITTED[key] = (eng, state)
    return _ADMITTED[key]


def _live_kv(cache, mgr, row):
    """The K/V of ``row``'s mapped positions, gathered through its pages."""
    pos = np.arange(mgr.length(row))
    pages = np.asarray(mgr.page_table([row]))[0][pos // mgr.page_size]
    return {k: np.asarray(v)[:, pages, pos % mgr.page_size]
            for k, v in cache.items()}


@pytest.mark.parametrize("J", [1, 2])
@pytest.mark.parametrize("mode", ["jit", "jit+donate"])
def test_compiled_prefill_matches_eager(mode, J):
    """Admissions through the jitted prefill step commit the same tokens as
    the eager prefill, leave the same K/V in every live slot of both pools,
    and keep the allocators consistent."""
    eng, state = _admit_and_serve(mode, J)
    ref, ref_state = _admit_and_serve("eager", J)
    assert ({M for _, M in eng.prefill_shapes}
            == {M for _, M in ref.prefill_shapes} == {16, 32})
    assert ([list(map(int, c)) for c in state.committed]
            == [list(map(int, c)) for c in ref_state.committed])
    for pool, ref_pool, mgr, ref_mgr in (
            (eng.t_cache, ref.t_cache, eng.t_pages, ref.t_pages),
            (eng.d_cache, ref.d_cache, eng.d_pages, ref.d_pages)):
        mgr.check_invariants()
        assert mgr.streams() == ref_mgr.streams()
        for row in mgr.streams():
            assert mgr.length(row) == ref_mgr.length(row)
            got, want = _live_kv(pool, mgr, row), _live_kv(ref_pool, ref_mgr,
                                                           row)
            for leaf in want:
                np.testing.assert_array_equal(got[leaf], want[leaf])


@pytest.mark.parametrize("mode", ["jit", "jit+donate"])
def test_compiled_prefill_traces_once_per_bucket(mode):
    """A re-admission at a warmed prompt bucket runs the compiled prefill
    with no retrace (no new ``("prefill", n, Mb)`` step shape, no
    ``engine.prefill_trace``); under ``jit+donate`` it donates the pools
    and the engine adopts the step's live outputs."""
    from repro.obs import trace

    tcfg, dcfg = _engine_pair()
    eng = SpecEngine(tcfg, dcfg, max_len=96, cache_kind="paged",
                     num_pages=240, compile_mode=mode)
    eng.init_params(jax.random.PRNGKey(0))
    state = eng.start(jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                         tcfg.vocab_size))
    with trace.tracing() as tr:
        state, _ = eng.add_streams(state, jax.random.randint(
            jax.random.PRNGKey(2), (1, 11), 0, tcfg.vocab_size))
    assert tr.counters()["engine.prefill_trace"] == 1
    assert ("prefill", 1, 16) in eng.step_shapes
    assert eng.prefill_shapes == {(1, 8), (1, 16)}
    old = jax.tree.leaves((eng.t_cache, eng.d_cache))
    retraced, shapes = [], set(eng.step_shapes)
    eng.on_step_trace = eng.on_prefill_trace = retraced.append
    with trace.tracing() as tr:
        state, rows = eng.add_streams(state, jax.random.randint(
            jax.random.PRNGKey(3), (1, 13), 0, tcfg.vocab_size))
    assert retraced == [] and eng.step_shapes == shapes
    assert tr.counters()["engine.admit_host_sync"] == 1
    assert "engine.prefill_trace" not in tr.counters()
    new = jax.tree.leaves((eng.t_cache, eng.d_cache))
    assert not any(x.is_deleted() for x in new)
    deleted = [x.is_deleted() for x in old]
    assert all(deleted) if mode == "jit+donate" else not any(deleted)
    assert state.committed[rows[0]][-1] == int(state.pending[rows[0]])
    eng.t_pages.check_invariants()
    eng.d_pages.check_invariants()
