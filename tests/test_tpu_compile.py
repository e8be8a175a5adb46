"""Compile rehearsals of the served path's Pallas kernels for a TPU v5e.

Each case compiles one hot-path kernel at the real widths of the served
pair (target ``qwen2.5-3b``, draft ``qwen3.5-0.8b``, bf16) for one chip of
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
