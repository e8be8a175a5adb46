"""Compile rehearsals of the served path's Pallas kernels for a TPU v5e.

Each case compiles one hot-path kernel at the real widths of the served
pair (target ``qwen2.5-3b``, draft ``qwen3.5-0.8b``, bf16), and of the
Moonlight target's latent attention and expert matmul, for one chip of
a described ``v5e:2x2`` topology, with the TPU compiler installed here and
no chip attached.  The compiler refuses what interpret mode accepts (block
shapes off the chip's tiling, scalars stored to vector memory, primitives
the kernel language lacks), so these guard every later change to the
kernels.  Nothing runs: shapes only.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.fused_verify_sample import fused_verify_sample_pallas
from repro.kernels.gather_softmax_prob import gather_softmax_prob_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.residual_sample import residual_sample_pallas
from repro.kernels.tree_attention import paged_tree_attention_pallas

B, PAGE, MAX_LEN, L, VHAT = 8, 16, 1024, 8, 64
N_SLOTS = MAX_LEN // PAGE
N_PAGES = B * 2 * N_SLOTS          # the gateway's pool sizing
PAIR = ("qwen2.5-3b", "qwen3.5-0.8b")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _attention_args(sharding, arch: str, T: int, tree: bool):
    cfg = get_config(arch)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    args = [S((B, T, H, D), jnp.bfloat16),
            S((N_PAGES, PAGE, KV, D), jnp.bfloat16),
            S((N_PAGES, PAGE, KV, D), jnp.bfloat16),
            S((B, N_SLOTS), jnp.int32), S((B,), jnp.int32)]
    if tree:
        args.append(S((B, T, T), jnp.bool_))
    return args


def _verify_args(sharding, kernel: str):
    V = get_config(PAIR[0]).vocab_size
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    if kernel == "fused_verify_sample":
        return [S((B, L + 1, V), jnp.bfloat16), S((B, L), jnp.int32),
                S((B, L), jnp.float32), S((B, L, VHAT), jnp.int32),
                S((B, L, VHAT), jnp.float32), S((B, L), jnp.float32),
                S((B,), jnp.float32), S((B,), jnp.int32)]
    if kernel == "residual_sample":
        return [S((B, V), jnp.float32), S((B, V), jnp.float32),
                S((B,), jnp.float32)]
    return [S((B * L, V), jnp.bfloat16), S((B * L,), jnp.int32)]


CASES = ([("paged_attention", arch, T) for arch in PAIR for T in (1, 9)]
         + [("paged_tree_attention", arch, 17) for arch in PAIR]
         + [(k, PAIR[0], None) for k in ("fused_verify_sample",
                                         "residual_sample",
                                         "gather_softmax_prob")])

KERNELS = {
    "paged_attention": paged_attention_pallas,
    "paged_tree_attention": paged_tree_attention_pallas,
    "fused_verify_sample": fused_verify_sample_pallas,
    "residual_sample": residual_sample_pallas,
    "gather_softmax_prob": gather_softmax_prob_pallas,
}


@pytest.mark.parametrize("kernel,arch,T", CASES,
                         ids=[f"{k}-{a}-T{t}" if t else k
                              for k, a, t in CASES])
def test_kernel_compiles_for_v5e(one_chip, kernel, arch, T):
    if T is None:
        args = _verify_args(one_chip, kernel)
    else:
        args = _attention_args(one_chip, arch, T,
                               tree=kernel == "paged_tree_attention")
    compiled = KERNELS[kernel].lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# Moonlight-16B-A3B (registry ``moonshot-v1-16b-a3b``): the paged latent
# attention of J=1 windows, and the grouped expert matmul of a verify pass
# (8 x 9 tokens, top 6: 432 rows, tile 16) and of a 256-token prefill
# (1530 rows, tile 64) over the 16 experts one chip holds
LATENT_CASES = [("latent_attention", T) for T in (1, 9)] + [
    ("expert_matmul", M) for M in (432, 1530)]


@pytest.mark.parametrize("kernel,n", LATENT_CASES,
                         ids=[f"{k}-{n}" for k, n in LATENT_CASES])
def test_moonlight_kernel_compiles_for_v5e(one_chip, kernel, n):
    from repro.kernels.expert_matmul import expert_matmul_pallas
    from repro.kernels.latent_attention import latent_attention_pallas

    cfg = get_config("moonshot-v1-16b-a3b")
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    bf = jnp.bfloat16
    if kernel == "latent_attention":
        fn = jax.jit(lambda *a: latent_attention_pallas(
            *a, dv=cfg.kv_lora_rank, scale=cfg.head_dim ** -0.5))
        args = [S((B, n, cfg.num_heads, cfg.latent_dim), bf),
                S((N_PAGES, PAGE, cfg.latent_dim), bf),
                S((B, N_SLOTS), jnp.int32), S((B,), jnp.int32)]
    else:
        d, F, E = cfg.d_model, cfg.moe_d_ff, 16
        fn = jax.jit(lambda *a: expert_matmul_pallas(
            *a, tm=16 if n < 1024 else 64))
        args = [S((n, d), bf), S((E, d, F), bf), S((E, d, F), bf),
                S((E, F, d), bf), S((E,), jnp.int32)]
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
