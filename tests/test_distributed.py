"""Distributed runtime tests: sharding rules, compression, fault tolerance.

Multi-device behaviour (8 fake CPU devices) runs in a subprocess so the main
test process keeps its single-device view.
"""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.distributed.collectives import (
    compress_gradients_topk,
    compression_ratio,
    dequantize_int8,
    init_error_feedback,
    quantize_int8,
    topk_compress,
    topk_decompress,
)
from repro.distributed.fault_tolerance import (
    RecoveryPlan,
    degraded_mesh_plan,
    expansion_mesh_plan,
    straggler_policy,
)


# ---------------------------------------------------------------------------
# Sharding rules (structural: specs valid for every arch without devices)
# ---------------------------------------------------------------------------

def _fake_mesh_shapes():
    """AbstractMesh stand-in: rule functions only read .shape/.axis_names."""
    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}
    return FakeMesh()


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_specs_cover_all_leaves(arch):
    from repro.distributed.sharding import param_spec
    from repro.models import build_model

    cfg = get_config(arch)
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    mesh = _fake_mesh_shapes()
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    n_model_sharded = 0
    for path, leaf in flat:
        pstr = "/".join(str(getattr(p, "key", p)) for p in path)
        spec = param_spec(pstr, leaf.shape, mesh)
        assert len(spec) <= len(leaf.shape)
        # every named axis must divide its dim
        for dim, s in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if s is None:
                continue
            axes = (s,) if isinstance(s, str) else s
            ways = 1
            for a in axes:
                ways *= mesh.shape[a]
            assert dim % ways == 0, (arch, pstr, leaf.shape, spec)
        if "model" in str(spec):
            n_model_sharded += 1
    assert n_model_sharded > 0, f"{arch}: nothing is tensor-parallel"


@pytest.mark.parametrize("arch", ["arctic-480b", "moonshot-v1-16b-a3b"])
def test_moe_experts_sharded_over_model(arch):
    from repro.distributed.sharding import param_spec

    cfg = get_config(arch)
    mesh = _fake_mesh_shapes()
    E, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    spec = param_spec("blocks/moe/w_gate", (47, E, d, f), mesh)
    assert tuple(spec)[1] == "model"  # expert-parallel


def test_per_chip_param_bytes_fit_hbm():
    """480B-class training state must fit 16GB/chip under the rules."""
    from repro.distributed.sharding import param_spec
    from repro.models import build_model

    cfg = get_config("arctic-480b")
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    mesh = _fake_mesh_shapes()
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    per_chip = 0.0
    for path, leaf in flat:
        pstr = "/".join(str(getattr(p, "key", p)) for p in path)
        spec = param_spec(pstr, leaf.shape, mesh)
        ways = 1
        for s in spec:
            if s is None:
                continue
            axes = (s,) if isinstance(s, str) else s
            for a in axes:
                ways *= mesh.shape[a]
        per_chip += np.prod(leaf.shape) / ways
    # bf16 params + bf16 moments (arctic dry-run optimizer) = 6 bytes/param
    assert per_chip * 6 < 16e9, f"{per_chip * 6 / 1e9:.1f} GB/chip"


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------

def test_topk_roundtrip_preserves_big_entries():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 32)))
    idx, vals, residual = topk_compress(x, 0.1)
    dec = topk_decompress(idx, vals, x.shape)
    flat = np.abs(np.asarray(x)).ravel()
    thresh = np.sort(flat)[-int(flat.size * 0.1)]
    big = np.abs(np.asarray(x)) >= thresh
    np.testing.assert_allclose(np.asarray(dec)[big], np.asarray(x)[big],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dec + residual), np.asarray(x),
                               rtol=1e-6)


def test_error_feedback_accumulates():
    """With error feedback, repeated compression of a CONSTANT gradient must
    pass the full magnitude through over time (no systematic bias)."""
    g = {"w": jnp.asarray(np.random.default_rng(1).normal(size=(256,)))}
    ef = init_error_feedback(g)
    total = jnp.zeros_like(g["w"])
    n = 200  # ≫ rotation period 1/frac = 20 so the EF bias averages out
    for _ in range(n):
        comp, ef, effective = compress_gradients_topk(g, ef, 0.05)
        total = total + effective["w"]
    # mean transmitted per step -> g as steps grow
    np.testing.assert_allclose(np.asarray(total / n), np.asarray(g["w"]),
                               atol=0.12)


def test_compression_ratio():
    g = {"w": jnp.ones((1000,))}
    ef = init_error_feedback(g)
    comp, _, _ = compress_gradients_topk(g, ef, 0.01)
    assert compression_ratio(comp) < 0.05


def test_int8_quantization_error_bounded():
    x = jnp.asarray(np.random.default_rng(2).normal(size=(4096,)))
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, scale) - x))
    assert err.max() <= float(scale) * 0.5 + 1e-7


def test_int8_allreduce_multidevice_subprocess():
    """Real shard_map int8 all-reduce on 8 fake devices."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        import sys; sys.path.insert(0, "src")
        from repro.distributed.collectives import make_compressed_allreduce
        mesh = jax.make_mesh((8,), ("data",))
        fn = make_compressed_allreduce(mesh, "data")
        x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 128)))
        got = fn(x)
        want = np.mean(np.asarray(x), axis=0)
        np.testing.assert_allclose(np.asarray(got), want, atol=0.05)
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=".", timeout=300)
    assert "OK" in out.stdout, out.stderr[-2000:]


# ---------------------------------------------------------------------------
# Fault tolerance / elasticity
# ---------------------------------------------------------------------------

def test_degraded_mesh_drops_data_rows():
    plan = degraded_mesh_plan((2, 16, 16), ("pod", "data", "model"),
                              failed_chips=3, chips_per_host=4)
    assert plan.shape == (2, 15, 16)
    assert plan.batch_scale == pytest.approx(16 / 15)


def test_degraded_mesh_multiple_hosts():
    plan = degraded_mesh_plan((16, 16), ("data", "model"), failed_chips=40,
                              chips_per_host=4)
    assert plan.shape == (13, 16)


def test_degraded_mesh_unrecoverable():
    with pytest.raises(RuntimeError):
        degraded_mesh_plan((2, 16), ("data", "model"), failed_chips=64,
                           chips_per_host=4)


def test_expansion_plan():
    plan = expansion_mesh_plan((14, 16), ("data", "model"), new_chips=32)
    assert plan.shape == (16, 16)


def test_recovery_plan_uses_latest_checkpoint():
    plan = degraded_mesh_plan((16, 16), ("data", "model"), 4)
    rec = RecoveryPlan.build(plan, [100, 300, 200])
    assert rec.restore_step == 300
    assert rec.resume_data_step == 300


def test_straggler_detection():
    times = np.ones((8, 10)) * 0.1
    times[3] *= 5.0                         # persistent straggler
    out = straggler_policy(times)
    assert list(out["stragglers"]) == [3]
    assert out["action"] == "drain-and-redistribute"
    # a single slow step is NOT a straggler
    times2 = np.ones((8, 10)) * 0.1
    times2[2, 4] = 3.0
    assert len(straggler_policy(times2)["stragglers"]) == 0


def test_elastic_resharding_subprocess():
    """Shrink 8->6 devices: params restored from checkpoint re-shard onto the
    degraded mesh and a jitted matmul still runs."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        import sys; sys.path.insert(0, "src")
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.distributed.fault_tolerance import degraded_mesh_plan

        auto = (AxisType.Auto, AxisType.Auto)
        w = np.arange(48.0, dtype=np.float32).reshape(8, 6)
        mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=auto)
        sharded = jax.device_put(w, NamedSharding(mesh, P("data", "model")))
        plan = degraded_mesh_plan((4, 2), ("data", "model"), failed_chips=2,
                                  chips_per_host=2)
        assert plan.shape == (3, 2), plan.shape
        new_mesh = jax.make_mesh(plan.shape, plan.axis_names,
                                 devices=np.array(jax.devices()[:6]),
                                 axis_types=auto)
        # checkpoint-restore path: host roundtrip then re-place
        host = np.asarray(sharded)
        resharded = jax.device_put(host, NamedSharding(new_mesh, P(None, "model")))
        y = jax.jit(lambda a: (a @ a.T).sum())(resharded)
        np.testing.assert_allclose(float(y), float((w @ w.T).sum()), rtol=1e-6)
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=".", timeout=300)
    assert "OK" in out.stdout, out.stderr[-2000:]
