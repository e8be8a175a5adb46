"""Compiled round path (``repro.serving.compiled``): jitted step parity,
donation safety, retrace bounds, and host-transfer accounting.

The engine's compiled modes must be *bit-identical* to eager dispatch — the
jitted steps run the same ops at the same shapes with the same RNG stream —
so every parity assertion here is exact equality on committed token ids,
not a tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import CellConfig, EngineBackend, MultiSpinCell, Request
from repro.configs import get_config
from repro.models import build_model
from repro.serving import SpecEngine
from repro.serving.compiled import COMPILE_MODES, build_round_steps

B, L, VHAT = 3, 4, 64
MAX_LEN = 96


def _engine(mode, cache_kind="paged", seed=0):
    tcfg = get_config("qwen2.5-3b").smoke()
    dcfg = tcfg.replace(num_layers=1, d_model=32, num_heads=2, num_kv_heads=1,
                        head_dim=16, d_ff=64, name="draft-smoke")
    kw = {"num_pages": B * 2 * (MAX_LEN // 16)} if cache_kind == "paged" else {}
    eng = SpecEngine(tcfg, dcfg, max_len=MAX_LEN, cache_kind=cache_kind,
                     compile_mode=mode, **kw)
    eng.init_params(jax.random.PRNGKey(seed))
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, 10), 0,
                                 tcfg.vocab_size)
    return eng, prompts


def _run(mode, cache_kind, rounds=4, widths=(1,)):
    """Run ``rounds`` rounds (draft_width cycling through ``widths``) and
    return (engine, state, host_syncs_per_round for the J=1 rounds)."""
    eng, prompts = _engine(mode, cache_kind)
    st = eng.start(prompts)
    if mode != "eager":
        st, info = eng.warmup(st, [(B, L)], vhat=VHAT)
        assert info, "warmup compiled nothing"
    base = jax.random.PRNGKey(42)
    lin_syncs = []
    for r in range(rounds):
        J = widths[r % len(widths)]
        h0 = eng.host_syncs
        st, _, _ = eng.spin_round(st, np.full(B, L), jax.random.fold_in(base, r),
                                  vhat=VHAT, draft_width=J)
        if J == 1:
            lin_syncs.append(eng.host_syncs - h0)
    if cache_kind == "paged":
        eng.t_pages.check_invariants()
        eng.d_pages.check_invariants()
    return eng, st, lin_syncs


_COMMITTED = {}


def _committed(mode, cache_kind, widths=(1,)):
    key = (mode, cache_kind, widths)
    if key not in _COMMITTED:
        _, st, _ = _run(mode, cache_kind, widths=widths)
        _COMMITTED[key] = [list(map(int, c)) for c in st.committed]
    return _COMMITTED[key]


@pytest.mark.parametrize("cache_kind", ["paged", "contiguous"])
@pytest.mark.parametrize("mode", ["jit", "jit+donate"])
def test_compiled_bit_identical_to_eager(mode, cache_kind):
    assert _committed(mode, cache_kind) == _committed("eager", cache_kind)


def test_mixed_width_rounds_bit_identical():
    """J=1 rounds run the linear compiled steps; J>1 rounds run the tree
    steps (forest, tree verify, scatter commit).  Alternating them through
    one engine must still match eager exactly — the caches each kind of
    step donates and adopts have to interoperate."""
    widths = (1, 2, 1, 2)
    assert _committed("jit+donate", "paged", widths) \
        == _committed("eager", "paged", widths)


@pytest.mark.parametrize("mode", ["jit", "jit+donate"])
def test_compiled_tree_rounds_bit_identical_to_eager(mode):
    assert _committed(mode, "paged", (2,)) == _committed("eager", "paged",
                                                         (2,))


def test_tree_rounds_trace_once_per_bucket():
    """A tree round runs three jitted steps per (B, J, L) bucket; repeating
    the bucket must not retrace them."""
    eng, prompts = _engine("jit+donate")
    st = eng.start(prompts)
    base = jax.random.PRNGKey(3)
    st, _, _ = eng.spin_round(st, np.full(B, L), base, vhat=VHAT,
                              draft_width=2)
    assert {k for k in eng.step_shapes if k[0] != "commit"} == {
        ("prefill", B, 16), ("forest", B, L), ("tree_verify", B, L),
        ("tree_commit", B, L)}
    retraced = []
    eng.on_step_trace = retraced.append
    for r in range(2):
        st, _, _ = eng.spin_round(st, np.full(B, L),
                                  jax.random.fold_in(base, r + 1),
                                  vhat=VHAT, draft_width=2)
    assert retraced == [], f"retraced: {retraced}"


@pytest.mark.parametrize("L_step", [1, L])
def test_donated_draft_step_updates_pools_in_place(L_step):
    """The draft step runs L+1 window passes over the donated pool; each
    layer writes its slice back into the carried stack, so the compiled
    step needs less scratch than one copy of the pool.  (Scanned over as
    inputs, the pool came back as a fresh copy per pass: several pools of
    temporaries, more than a full-width pair leaves free on one chip.)"""
    model = build_model(get_config("qwen3.5-0.8b").smoke())
    steps = build_round_steps(model, model, mode="jit+donate")
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pool = jax.eval_shape(lambda: model.init_paged_cache(512, 16,
                                                         jnp.float32))
    pt = jax.ShapeDtypeStruct((B, 32), np.int32)
    iv = jax.ShapeDtypeStruct((B,), np.int32)
    compiled = steps.draft.lower(params, pool, pt, iv, iv,
                                 jax.random.PRNGKey(0), L=L_step,
                                 vhat=VHAT).compile()
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes, (mem.temp_size_in_bytes,
                                                 pool_bytes)


@pytest.mark.parametrize("mode", COMPILE_MODES)
def test_one_host_sync_per_linear_round(mode):
    _, _, lin_syncs = _run(mode, "paged")
    assert lin_syncs == [1] * len(lin_syncs), lin_syncs


def test_warmup_bounds_retraces():
    """``warmup(buckets)`` pre-traces draft/verify/commit at each bucket
    (``start()`` has traced the prefill at its prompt bucket); real rounds
    at those shapes must not retrace (shape-keyed, counted by the
    trace-time ``on_step_trace`` hook)."""
    eng, prompts = _engine("jit+donate")
    st = eng.start(prompts)
    st, _ = eng.warmup(st, [(B, L)], vhat=VHAT)
    assert eng.step_shapes == {("prefill", B, 16), ("draft", B, L),
                               ("verify", B, L), ("commit", B, L)}
    retraced = []
    eng.on_step_trace = retraced.append
    base = jax.random.PRNGKey(7)
    for r in range(3):
        st, _, _ = eng.spin_round(st, np.full(B, L), jax.random.fold_in(base, r),
                                  vhat=VHAT)
    assert retraced == [], f"retraced after warmup: {retraced}"


def test_dispatch_is_transfer_free():
    """After warmup, the draft+verify dispatch path moves nothing between
    host and device: stream state is device-resident and the page table's
    device mirror updates incrementally.  (Commit is excluded — its packed
    emission is the round's ONE intentional device->host fetch.)"""
    eng, prompts = _engine("jit+donate")
    st = eng.start(prompts)
    st, _ = eng.warmup(st, [(B, L)], vhat=VHAT)
    kd, kv = jax.random.split(jax.random.PRNGKey(99))
    with jax.transfer_guard("disallow"):
        ticket = eng.draft_rows(st, list(range(B)), np.full(B, L), kd,
                                vhat=VHAT)
        ticket = eng.verify_rows(ticket, kv)
    st, accepted = eng.commit_rows(st, ticket)
    assert np.all(accepted >= 1)


def test_roundrecord_reports_host_syncs():
    """The cell's per-round telemetry carries the engine's host-transfer
    count: exactly one device->host fetch per committed round.  Eager mode
    keeps this test cheap — the commit math and its packed-emission fetch
    are shared by every compile mode (the per-mode sync count is asserted
    by ``test_one_host_sync_per_linear_round``)."""
    eng, prompts = _engine("eager")
    backend = EngineBackend(eng, eng.start(prompts))
    cfg = CellConfig(scheme="hete", t_ver_fix=0.03, t_ver_lin=0.002,
                     L_max=L, max_batch=B, seed=0)
    cell = MultiSpinCell(cfg, backend=backend, rng=np.random.default_rng(0))
    for i in range(B):
        cell.submit(Request(rid=i, prompt_len=6, max_new_tokens=10 ** 9,
                            alpha=0.8, T_S=0.03))
    cell.admit()
    for _ in range(3):
        rec = cell.step()
        assert rec.n_host_syncs == 1, rec.n_host_syncs


def test_invalid_compile_mode_rejected():
    tcfg = get_config("qwen2.5-3b").smoke()
    with pytest.raises(ValueError):
        SpecEngine(tcfg, tcfg, max_len=32, compile_mode="aot")
