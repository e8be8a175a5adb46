"""Compile each configuration's round steps and prompt buckets for one chip
of a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 bench/checks/compile_v5e.py [config ...]

Uses the TPU compiler installed here against ``v5e:2x2``'s first device,
with the Pallas kernels selected, at the configuration's real widths and
the cell's shapes: the J=1 draft and verify steps (or the J=2 forest and
tree-verify steps) at the smallest and largest draft length, and each
model's prefill at the buckets 64-512 (the cells use up to 256; 512 shows
the refusal).  It prints ``memory_analysis()`` of each program, or the
compiler's refusal.
Nothing runs, so it says nothing about time or results.
"""

import json
import os
import sys
import time
import traceback
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["REPRO_KERNELS"] = "pallas"
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import arch  # noqa: E402

GiB = 2 ** 30
PREFILL_BUCKETS = (64, 128, 256, 512)   # 512 is refused (PERF.md)


def main(names):
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    shard = SingleDeviceSharding(topo.devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=shard)  # noqa: E731
    put = lambda tree: jax.tree.map(lambda a: S(a.shape, a.dtype), tree)  # noqa: E731
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for entry in spec["configs"]:
        if names and entry["name"] not in names:
            continue
        config = json.loads((BENCH.parent / entry["file"]).read_text())
        srv = config["serving"]
        from repro.models import build_model
        from repro.serving.compiled import build_round_steps
        t_arch, d_arch = arch.of(config["target"]), arch.of(config["draft"])
        tcfg = t_arch.model_config(config["target"], "t", srv["dtype"])
        dcfg = d_arch.model_config(config["draft"], "d", srv["dtype"])
        target, draft = build_model(tcfg), build_model(dcfg)
        steps = build_round_steps(target, draft, mode=srv["compile"])
        B, ps, max_len, vhat = (srv["max_batch"], srv["page_size"],
                                srv["max_len"], srv["vhat"])
        slots = max_len // ps
        pages = B * 2 * slots
        J = srv.get("draft_width", 1)
        acc = config["acceptance"]
        key = jax.random.PRNGKey(0)
        tp = put(jax.eval_shape(lambda: t_arch.make_params(
            config["target"], acc, key, srv["dtype"])))
        dp = put(jax.eval_shape(lambda: d_arch.make_params(
            config["draft"], acc, key, srv["dtype"])))
        tkv = put(jax.eval_shape(lambda: target.init_paged_cache(
            pages, ps, jnp.bfloat16)))
        dkv = put(jax.eval_shape(lambda: draft.init_paged_cache(
            pages, ps, jnp.bfloat16)))
        pt = S((B, slots), jnp.int32)
        vec = S((B,), jnp.int32)
        k = S((2,), jnp.uint32)
        jobs = []
        for L in (1, srv["L_max"]):
            if J == 1:
                jobs.append((f"draft_step L={L}", steps.draft,
                             (dp, dkv, pt, vec, vec, k), dict(L=L, vhat=vhat)))
                jobs.append((f"verify_step L={L}", steps.verify,
                             (tp, tkv, pt, vec, S((B, L), jnp.int32),
                              S((B, L), jnp.float32),
                              S((B, L, vhat), jnp.int32),
                              S((B, L, vhat), jnp.float32), vec, vec, k), {}))
            else:
                W = J * L
                jobs.append((f"forest_step J={J} L={L}", steps.forest,
                             (dp, dkv, pt, vec, vec, k),
                             dict(L=L, J=J, vhat=vhat, keep_windows=True)))
                jobs.append((f"tree_verify_step J={J} L={L}",
                             steps.tree_verify,
                             (tp, tkv, pt, S((B, W + 1), jnp.int32),
                              S((B, W + 1, W + 1), jnp.bool_),
                              S((B, W + 1), jnp.int32), vec, k,
                              S((B, W), jnp.int32), S((B, W), jnp.int32),
                              S((B, W), jnp.int32), S((B, W), jnp.float32),
                              S((B, J, L), jnp.int32),
                              S((B, W, vhat), jnp.int32),
                              S((B, W, vhat), jnp.float32), vec), {}))
        for Mb in PREFILL_BUCKETS:
            for name, model, p, kv in (("target", target, tp, tkv),
                                       ("draft", draft, dp, dkv)):
                def prefill(p, toks, kv, pt, model=model):
                    return model.prefill(p, toks, dict(kv, pages=pt))
                jobs.append((f"{name} prefill bucket {Mb}", jax.jit(prefill),
                             (p, S((1, Mb - 1), jnp.int32), kv,
                              S((1, slots), jnp.int32)), {}))
        for label, fn, args, kw in jobs:
            t0 = time.perf_counter()
            try:
                c = fn.lower(*args, **kw).compile()
                m = c.memory_analysis()
                print(f"{config['name']} {label}: compiled in "
                      f"{time.perf_counter() - t0:.1f} s; arguments "
                      f"{m.argument_size_in_bytes / GiB:.3f} GiB, outputs "
                      f"{m.output_size_in_bytes / GiB:.3f} GiB, aliased "
                      f"{m.alias_size_in_bytes / GiB:.3f} GiB, temporaries "
                      f"{m.temp_size_in_bytes / GiB:.3f} GiB", flush=True)
            except Exception as e:  # the compiler's refusal is the finding
                print(f"{config['name']} {label}: REFUSED after "
                      f"{time.perf_counter() - t0:.1f} s: "
                      f"{type(e).__name__}: {str(e)[:2000]}", flush=True)
                traceback.print_exc(limit=3)


if __name__ == "__main__":
    main(sys.argv[1:])
