"""A configuration brings its own architecture: CPU checks at smoke size.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/checks/test_archs.py

(a) both configuration files resolve their sections' ``"arch"``, and the
    ``ModelConfig`` each builds has the fields written here (those the
    benchmark built before architectures were modules);
(b) smoke-size weights through ``archs/qwen2.py`` match checksums recorded
    from the dense builder that ``weights.py`` held before;
(c) ``roofline.round_work`` / ``prefill_work`` of fixed synthetic rounds
    equal the counts recorded from that same earlier code;
(d) an architecture given only as new files in a temporary directory (an
    untied-head variant of the smoke Qwen2 pair: an architecture module, a
    reference and a configuration) runs the CPU rehearsal with ``correct``
    true, and no file of ``bench/`` is touched.
"""

import dataclasses
import hashlib
import json
import os
import sys
import textwrap
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("REPRO_KERNELS", "interpret")
BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH / "checks", BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import arch  # noqa: E402
import roofline  # noqa: E402
import weights  # noqa: E402
from archs import qwen2  # noqa: E402
from recorder import Round  # noqa: E402

CONFIGS = ("qwen2.5-3b.d0.5b", "qwen2.5-1.5b.d0.5b.tree")


def load(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def dense(name, layers, d, heads, head_dim, ff):
    return dict(name=name, family="dense", num_layers=layers, d_model=d,
                num_heads=heads, num_kv_heads=2, head_dim=head_dim, d_ff=ff,
                vocab_size=151936, activation="swiglu", norm="rmsnorm",
                qkv_bias=True, rope_theta=1000000.0, tie_embeddings=True,
                param_dtype="bfloat16", compute_dtype="bfloat16")


MODEL_CONFIGS = {
    ("qwen2.5-3b.d0.5b", "target"):
        dense("qwen2.5-3b.d0.5b.target", 36, 2048, 16, 128, 11008),
    ("qwen2.5-3b.d0.5b", "draft"):
        dense("qwen2.5-3b.d0.5b.draft", 24, 896, 14, 64, 4864),
    ("qwen2.5-1.5b.d0.5b.tree", "target"):
        dense("qwen2.5-1.5b.d0.5b.tree.target", 28, 1536, 12, 128, 8960),
    ("qwen2.5-1.5b.d0.5b.tree", "draft"):
        dense("qwen2.5-1.5b.d0.5b.tree.draft", 24, 896, 14, 64, 4864),
}


@pytest.mark.parametrize("name,role", sorted(MODEL_CONFIGS))
def test_model_config_unchanged(name, role):
    from repro.configs.base import ModelConfig

    section = load(name)[role]
    assert arch.of(section) is qwen2
    got = arch.of(section).model_config(section, f"{name}.{role}", "bfloat16")
    want = ModelConfig(**MODEL_CONFIGS[name, role])
    assert dataclasses.asdict(got) == dataclasses.asdict(want)


# sha256 over (key path, dtype, shape, bytes) of every leaf, in tree order
PARAM_SHA = {
    ("float32", "target"):
        "745af1f8aebf51598dcaf0c13444bef8a81dd16f2e689d828970edfb76b0d58d",
    ("float32", "draft"):
        "e36b8943f5e5c26a5ebc1d54438af082941211a86e670200627890b4d71aaafe",
    ("bfloat16", "target"):
        "a63f696a4306ab8d0558f8dc1210c688d04c91871de5c63e150a230be9711990",
    ("bfloat16", "draft"):
        "c0cb0422cb9ff8b585ddbfc08eec2da426f9b05e0c721640fb88e30bcd4ef464",
}


def digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("dtype,role", sorted(PARAM_SHA))
def test_qwen2_weights_bit_identical(dtype, role):
    acc = dict(load(CONFIGS[0])["acceptance"], code_dims=16)
    kt, kd = weights.pair_keys(12345678901)
    section, key = ((qwen2.smoke(2, 128, 4, 256), kt) if role == "target"
                    else (qwen2.smoke(1, 64, 4, 128), kd))
    params = arch.of(section).make_params(section, acc, key, dtype)
    assert digest(params) == PARAM_SHA[dtype, role]


def rounds():
    lengths = np.array([3, 8, 1, 5])
    live = {0: (11, 100), 1: (12, 37), 3: (13, 250)}
    tree = np.random.default_rng(0).integers(0, 3, (4, 2, 8)).astype(np.int32)
    return {1: Round(0.0, 1.0, None, lengths, np.zeros(4, bool), 1, live,
                     {"tokens": np.zeros((4, 8), np.int32)}),
            2: Round(0.0, 1.0, None, lengths, np.zeros(4, bool), 2, live,
                     {"tokens": tree})}


ROUND_WORK = {
    ("qwen2.5-3b.d0.5b", 1): (136906575872.0, [198610944.0, 30007296.0],
                              [680951808.0, 20570112.0], 19),
    ("qwen2.5-3b.d0.5b", 2): (249025536000.0, [397221888.0, 60014592.0],
                              [1259864064.0, 25546752.0], 19),
    ("qwen2.5-1.5b.d0.5b.tree", 1): (78021996544.0,
                                     [198610944.0, 30007296.0],
                                     [397221888.0, 14909440.0], 19),
    ("qwen2.5-1.5b.d0.5b.tree", 2): (143635914752.0,
                                     [397221888.0, 60014592.0],
                                     [734920704.0, 17920000.0], 19),
}
# (flops, attention flops, attention bytes) of both models together
PREFILL_WORK = {
    ("qwen2.5-3b.d0.5b", 2): (7159697408.0, 380928.0, 430080.0),
    ("qwen2.5-3b.d0.5b", 64): (451804889088.0, 767950848.0, 27095040.0),
    ("qwen2.5-3b.d0.5b", 257): (1845316026368.0, 12531007488.0,
                                110100480.0),
    ("qwen2.5-1.5b.d0.5b.tree", 2): (4075319296.0, 258048.0, 299008.0),
    ("qwen2.5-1.5b.d0.5b.tree", 64): (257249083392.0, 520224768.0,
                                      18837504.0),
    ("qwen2.5-1.5b.d0.5b.tree", 257): (1051704426496.0, 8488747008.0,
                                       76546048.0),
}


@pytest.mark.parametrize("name,J", sorted(ROUND_WORK))
def test_round_work_unchanged(name, J):
    w = roofline.round_work(load(name), rounds()[J])
    assert (w["flops"], w["draft_attn"], w["target_attn"],
            w["verify_positions"]) == ROUND_WORK[name, J]


@pytest.mark.parametrize("name,M", sorted(PREFILL_WORK))
def test_prefill_work_unchanged(name, M):
    w = roofline.prefill_work(load(name), M)
    flops, attn_flops, attn_bytes = PREFILL_WORK[name, M]
    assert w["flops"] == flops
    assert w["target_attn"][0] + w["draft_attn"][0] == attn_flops
    assert w["target_attn"][1] + w["draft_attn"][1] == attn_bytes


PROBE_ARCH = '''
"""Probe: the smoke Qwen2 pair with an untied output head ("unembed", which
carries the embedding's cluster codes)."""
import functools

import jax

import weights
from archs import qwen2

ATTENTION_KERNEL = qwen2.ATTENTION_KERNEL
model_config = qwen2.model_config
token_flops = qwen2.token_flops
attention = qwen2.attention


@functools.partial(jax.jit, static_argnames=("shape", "acc", "dtype"))
def _make(key, *, shape, acc, dtype):
    V, d = shape[0], shape[1]
    dt = weights.DTYPES[dtype]
    k_embed, k_code, k_blocks, k_head, k_lnf = jax.random.split(key, 5)
    return {"embed": weights.code_table(k_code, k_embed, V, d, acc, dt),
            "unembed": weights.code_table(k_code, k_head, V, d, acc, dt).T,
            "ln_f": {"scale": weights.final_scale(k_lnf, d, acc, dt)},
            "blocks": qwen2.blocks(k_blocks, shape,
                                   weights.live_dims(d, acc), dt)}


def make_params(section, acc, key, dtype="bfloat16"):
    return _make(key, shape=qwen2.shape(section),
                 acc=weights.acceptance_params(acc), dtype=dtype)
'''

PROBE_REFERENCE = '''
"""Probe reference: the Qwen2 reference with logits against "unembed"."""
from references import qwen2

hidden_states = qwen2.hidden_states


def sequence_log_probs(params, x_row, precision="f32"):
    return qwen2.log_probs(x_row, params["ln_f"]["scale"],
                           params["unembed"].T, precision=precision)
'''


def bench_files():
    return {p: p.stat().st_mtime_ns for p in BENCH.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_architecture_as_new_files_only(tmp_path):
    import harness
    from rehearse_cpu import smoke_cell

    (tmp_path / "archs").mkdir()
    (tmp_path / "references").mkdir()
    (tmp_path / "archs" / "probe_untied.py").write_text(
        textwrap.dedent(PROBE_ARCH))
    (tmp_path / "references" / "probe_untied.py").write_text(
        textwrap.dedent(PROBE_REFERENCE))
    spec, wl, config, traffic = smoke_cell("probe")
    config["reference"] = "probe_untied"
    for role in ("target", "draft"):
        config[role].update(arch="probe_untied", tie_word_embeddings=False)
    (tmp_path / "probe.json").write_text(json.dumps(config))
    before = bench_files()
    seen = {}

    def look(served):
        seen["tie"] = (served.tcfg.tie_embeddings, served.dcfg.tie_embeddings)
        seen["keys"] = set(served.engine.t_params) & set(served.engine.d_params)

    sys.path.insert(0, str(tmp_path))
    try:
        result = harness.run(
            ["--workload", "probe", "--seed", "20261019", "--seconds", "2",
             "--trace", "0"], allow_cpu=True,
            cell=(spec, wl, json.loads((tmp_path / "probe.json").read_text()),
                  traffic),
            hooks={"served": look})
    finally:
        sys.path.remove(str(tmp_path))
        for mod in ("archs.probe_untied", "references.probe_untied"):
            sys.modules.pop(mod, None)
    assert seen["tie"] == (False, False)
    assert "unembed" in seen["keys"]
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert bench_files() == before
