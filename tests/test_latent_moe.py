"""Multi-head latent attention and sigmoid-routed held experts (the DeepSeek-V3
layout of Moonlight-16B-A3B) against the benchmark's plain float32
reference, at a smoke size of the same shape: MLA with a rope part, one
leading dense layer, 4 experts held of a 16-wide router (top 4), two shared
experts and an untied head.

(a) paged prefill and J=1 windows through ``SpecEngine`` (compiled,
    donating) agree on logits with the reference's full forward pass;
(b) the routed parts of the 4 expert shares, plus the shared expert once,
    add up to the uncut reference layer;
(c) the paged latent-attention kernel (interpret mode) agrees with its
    oracle, with ragged lengths and unmapped pages;
(d) the dropless grouped dispatch agrees with a per-expert loop, also when
    every token goes to one expert;
and tree verification with an MLA target is refused, and the registry's
``moonshot-v1-16b-a3b`` is the published model.
"""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import weights  # noqa: E402
from archs import deepseek_v3, qwen2  # noqa: E402
from references import moonlight_qwen2 as reference  # noqa: E402

from repro.kernels import ref as kref  # noqa: E402
from repro.kernels.expert_matmul import expert_matmul_pallas  # noqa: E402
from repro.kernels.latent_attention import latent_attention_pallas  # noqa: E402
from repro.models.layers import routed_experts_apply  # noqa: E402
from repro.serving.spec_engine import SpecEngine  # noqa: E402

SECTION = deepseek_v3.smoke()
DRAFT = qwen2.smoke(1, 64, 4, 128)
ACC = dict(json.loads((BENCH / "configs" / "qwen2.5-3b.d0.5b.json")
                      .read_text())["acceptance"], code_dims=16)
SEED = 12345678901


def _engine(kernels, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", kernels)
    tcfg = deepseek_v3.model_config(SECTION, "t", "float32")
    dcfg = qwen2.model_config(DRAFT, "d", "float32")
    eng = SpecEngine(tcfg, dcfg, max_len=64, cache_kind="paged",
                     page_size=8, num_pages=32, compile_mode="jit+donate",
                     cache_dtype=jnp.float32)
    kt, kd = weights.pair_keys(SEED)
    eng.t_params = deepseek_v3.make_params(SECTION, ACC, kt, "float32")
    eng.d_params = qwen2.make_params(DRAFT, ACC, kd, "float32")
    return eng


@pytest.mark.parametrize("kernels", ["ref", "interpret"])
def test_paged_prefill_and_windows_match_reference(kernels, monkeypatch):
    """Prompts of 13 and 9 tokens prefilled through the compiled paged
    prefill, then two J=1 windows of 4 through the cache: every window
    logit equals the reference's full forward pass at that position.
    Tolerance: both sides are float32, but the program attends in the
    absorbed form against the latent pool and sums the experts in grouped
    order, so the sums differ in order; the logits carry the acceptance
    construction's code gain and reach |x| ~ 1e3 (rtol 2e-5, atol 2e-3,
    against a bfloat16 program that misses by tens)."""
    eng = _engine(kernels, monkeypatch)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, 13), rng.integers(0, 512, 9)]
    state = eng.start(jnp.asarray(prompts[0][None], jnp.int32))
    state, _ = eng.add_streams(state, jnp.asarray(prompts[1][None],
                                                  jnp.int32))
    window = jax.jit(eng.target.forward_window)
    seqs = [list(p) for p in prompts]
    got = [[], []]
    pos = np.array([len(p) - 1 for p in prompts])
    for _ in range(2):
        # the window: the pending token, then 3 new ones; one more new
        # token becomes the next window's pending token
        toks = rng.integers(0, 512, (2, 4))
        w = np.concatenate([[[s[-1]] for s in seqs], toks[:, :3]], axis=1)
        for b in range(2):
            eng.t_pages.extend(b, int(pos[b]) + 4)
        view = dict(eng.t_cache, pages=eng.t_pages.device_table([0, 1]))
        logits, cache = window(eng.t_params, jnp.asarray(w, jnp.int32), view,
                               jnp.asarray(pos, jnp.int32))
        eng.t_cache = {k: v for k, v in cache.items() if k != "pages"}
        for b in range(2):
            got[b].append(np.asarray(logits[b]))
            seqs[b] += list(toks[b])
        pos += 4
    for b in range(2):
        x = reference.hidden_states(eng.t_params, SECTION,
                                    jnp.asarray(seqs[b], jnp.int32)[None])
        want = np.asarray(reference.sequence_log_probs(eng.t_params, x[0]))
        mine = np.asarray(jax.nn.log_softmax(np.concatenate(got[b]), -1))
        start = len(prompts[b]) - 1
        np.testing.assert_allclose(mine, want[start:start + 8], rtol=2e-5,
                                   atol=2e-3)
    # the served round runs on the same engine: one host sync a round
    syncs = eng.host_syncs
    state, res, _ = eng.spin_round(state, np.array([3, 2]),
                                   jax.random.PRNGKey(1))
    assert eng.host_syncs == syncs + 1
    assert res.routing.shape == (2,)


def _moe_params(held, first, full):
    """One MoE layer's weights: the share [first, first + held) of the
    ``full`` parameter tree's experts, with the same router and shared
    expert."""
    return dict(full, **{k: full[k][first:first + held]
                         for k in ("w_gate", "w_up", "w_down")})


def test_four_expert_shares_add_up_to_the_uncut_layer():
    """Each share routes over all 16 experts and computes its 4 experts'
    part; the four routed parts plus the shared expert once equal the
    uncut reference layer (all 16 experts), and every (token, expert)
    pair is computed by exactly one share."""
    keys = jax.random.split(jax.random.PRNGKey(7), 9)
    d, F, E, K = 64, 32, 16, 4
    full = {"router": jax.random.normal(keys[0], (d, E)) / 8,
            "bias": jax.random.normal(keys[1], (E,)) * 0.1,
            "w_gate": jax.random.normal(keys[2], (E, d, F)) / 8,
            "w_up": jax.random.normal(keys[3], (E, d, F)) / 8,
            "w_down": jax.random.normal(keys[4], (E, F, d)) / 6,
            "shared": {"w_gate": jax.random.normal(keys[5], (d, 2 * F)) / 8,
                       "w_up": jax.random.normal(keys[6], (d, 2 * F)) / 8,
                       "w_down": jax.random.normal(keys[7], (2 * F, d)) / 8}}
    h = jax.random.normal(keys[8], (2, 24, d))
    with jax.default_matmul_precision("highest"):
        parts = [routed_experts_apply(_moe_params(4, s, full), h, top_k=K,
                                      routed_scale=2.446, first_expert=s)
                 for s in (0, 4, 8, 12)]
        shared = reference.swiglu(h, full["shared"], "f32")
    total = sum(y for y, _ in parts) - 3 * shared
    want = reference.experts(h, full, top_k=K, scale=2.446, first=0,
                             precision="f32")
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert sum(int(c[0]) for _, c in parts) == 2 * 24 * K


@pytest.mark.parametrize("T", [1, 4])
def test_latent_kernel_matches_oracle(T):
    """Ragged lengths (rows ending inside a page, a row inside its first
    page), unmapped slots between and after mapped ones."""
    k = jax.random.split(jax.random.PRNGKey(3), 2)
    B, H, C, dv, ps, P = 3, 4, 72, 64, 8, 12
    q = jax.random.normal(k[0], (B, T, H, C))
    pool = jax.random.normal(k[1], (P, ps, C))
    pt = jnp.asarray([[2, -1, 5, -1], [7, 1, 9, 3], [4, -1, -1, -1]],
                     jnp.int32)
    lengths = jnp.asarray([18, 27, 1], jnp.int32)
    want = kref.latent_attention_ref(q, pool, pt, lengths, dv=dv, scale=0.1)
    got = latent_attention_pallas(q, pool, pt, lengths, dv=dv, scale=0.1,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _loop(x, wg, wu, wd, sizes):
    out, start = [], 0
    for e, n in enumerate(sizes):
        xe = x[start:start + n]
        start += n
        out.append((jax.nn.silu(xe @ wg[e]) * (xe @ wu[e])) @ wd[e])
    return np.concatenate(out) if start else np.zeros((0, x.shape[1]))


@pytest.mark.parametrize("sizes", [(10, 0, 20, 5), (40, 0, 0, 0),
                                   (0, 0, 0, 40), (0, 0, 0, 0),
                                   (3, 3, 17, 1)])
def test_grouped_dispatch_matches_per_expert_loop(sizes):
    """The grouped matmul (Pallas interpret and the oracle) against a
    loop over experts, with a group holding every row, empty groups, and
    rows past the last group (pairs of experts held elsewhere: zero)."""
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    M, d, F, E = 44, 64, 256, 4
    x = jax.random.normal(k[0], (M, d))
    wg = jax.random.normal(k[1], (E, d, F)) / 8
    wu = jax.random.normal(k[2], (E, d, F)) / 8
    wd = jax.random.normal(k[3], (E, F, d)) / 16
    gs = jnp.asarray(sizes, jnp.int32)
    n = sum(sizes)
    want = _loop(np.asarray(x), wg, wu, wd, sizes)
    with jax.default_matmul_precision("highest"):
        for got in (kref.expert_matmul_ref(x, wg, wu, wd, gs),
                    expert_matmul_pallas(x, wg, wu, wd, gs, tm=16,
                                         interpret=True)):
            np.testing.assert_allclose(np.asarray(got)[:n], want,
                                       rtol=1e-4, atol=1e-4)
            assert not np.asarray(got)[n:].any()


def test_routed_layer_with_every_token_on_one_expert():
    """A bias that sends every token to experts 0-3 (held here): the
    dropless layer computes every pair, and matches the reference."""
    keys = jax.random.split(jax.random.PRNGKey(9), 6)
    d, F, E = 64, 32, 16
    m = {"router": jax.random.normal(keys[0], (d, E)) / 8,
         "bias": jnp.zeros((E,)).at[:4].set(100.0),
         "w_gate": jax.random.normal(keys[1], (4, d, F)) / 8,
         "w_up": jax.random.normal(keys[2], (4, d, F)) / 8,
         "w_down": jax.random.normal(keys[3], (4, F, d)) / 6}
    h = jax.random.normal(keys[4], (3, 10, d))
    m["shared"] = {"w_gate": jnp.zeros((d, 8)), "w_up": jnp.zeros((d, 8)),
                   "w_down": jnp.zeros((8, d))}
    with jax.default_matmul_precision("highest"):
        y, counts = routed_experts_apply(m, h, top_k=4, routed_scale=2.446)
    want = reference.experts(h, m, top_k=4, scale=2.446, first=0,
                             precision="f32")
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    assert [int(c) for c in counts] == [3 * 10 * 4, 4]


def test_tree_verification_refused_for_mla_target(monkeypatch):
    tcfg = deepseek_v3.model_config(SECTION, "t", "float32")
    dcfg = qwen2.model_config(DRAFT, "d", "float32")
    with pytest.raises(NotImplementedError, match="tree latent-attention"):
        SpecEngine(tcfg, dcfg, max_len=64, cache_kind="paged", page_size=8,
                   draft_width=2)
    eng = _engine("ref", monkeypatch)
    state = eng.start(jnp.zeros((2, 8), jnp.int32))
    with pytest.raises(NotImplementedError, match="tree latent-attention"):
        eng.spin_round(state, np.array([2, 2]), jax.random.PRNGKey(0),
                       draft_width=2)


def test_registry_entry_is_the_published_model():
    """The registry's Moonlight is the published config.json, and the
    benchmark's configuration differs from it only in its two cuts."""
    from repro.configs import get_config
    from repro.configs.moonshot_v1_16b_a3b import MOONLIGHT_16B_A3B

    cfg = get_config("moonshot-v1-16b-a3b")
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.vocab_size) == (
        27, 2048, 16, 163840)
    assert (cfg.attention, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == ("mla", 512, 128, 64,
                                                      128)
    assert (cfg.router, cfg.routed_scale, cfg.num_experts, cfg.top_k,
            cfg.num_experts_held, cfg.first_k_dense) == ("sigmoid", 2.446,
                                                         64, 6, 64, 1)
    assert (cfg.rope_theta, cfg.norm_eps, cfg.tie_embeddings) == (
        50000.0, 1e-5, False)
    config = json.loads((BENCH / "configs" / "moonlight-16b-a3b.ep4.d0.5b"
                         ".json").read_text())
    target = config["target"]
    changed = {k for k in MOONLIGHT_16B_A3B if target[k] !=
               MOONLIGHT_16B_A3B[k]}
    assert changed == {"n_routed_experts", "vocab_size"}
    assert all(config[k] == target[k] for k in MOONLIGHT_16B_A3B)
    served = deepseek_v3.model_config(target, "t", "bfloat16")
    assert (served.num_experts, served.num_experts_held,
            served.vocab_size) == (64, 16, 151936)
