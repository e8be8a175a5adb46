import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each assigned architecture and each applicable input shape, the matching
step function (train_step / prefill_step / serve_step) is jitted under the
production mesh with the repo's sharding rules, lowered from
ShapeDtypeStructs (no allocation), and compiled.  memory_analysis() proves
per-device fit; cost_analysis() + the partitioned HLO feed the roofline
table (EXPERIMENTS.md §Dry-run / §Roofline).

Results are cached per cell in experiments/dryrun/<arch>__<shape>__<mesh>.json
so interrupted sweeps resume where they stopped.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch phi4-mini-3.8b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED_ARCHS, SHAPES, applicable_shapes, get_config
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import build_model, has_prefix_embeds, input_specs
from repro.models.model_zoo import prefix_len
from repro.roofline.analysis import count_params, model_flops, roofline
from repro.training import OptimizerConfig, init_optimizer, make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")

# Per-cell overrides discovered during the perf pass live here; the baseline
# run uses the defaults.
TRAIN_MICROBATCHES = 4
# arctic-480b: params+opt already take 11.3 GB/chip at 256 chips; deep
# microbatching is the only way to approach fit (EXPERIMENTS.md §Dry-run)
ARCH_MICROBATCHES = {"arctic-480b": 16}


VOCAB_PAD = 2048  # 128 lanes x 16-way tensor parallelism


def _cfg_for_dryrun(arch: str, training: bool):
    cfg = get_config(arch)
    # pad vocab so the "vocab" logical axis shards over model (MaxText-style);
    # logits shrink 16x per chip and the embedding-grad transpose stays local.
    padded_vocab = -(-cfg.vocab_size // VOCAB_PAD) * VOCAB_PAD
    return cfg.replace(param_dtype="bfloat16", compute_dtype="bfloat16",
                       remat=training, vocab_size=padded_vocab)


def _sds(tree):
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               microbatches: int | None = None, fsdp: bool = True,
               moe_capacity: float | None = None, draft_window: int = 0,
               cache_dtype=None):
    """Returns (lowered, meta) for one dry-run cell."""
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    training = shape.kind == "train"
    cfg = _cfg_for_dryrun(arch, training)
    model = build_model(cfg)

    params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_sh = shd.param_shardings(mesh, params_shape, fsdp=fsdp)
    specs = input_specs(cfg, shape)
    b_sh = shd.batch_shardings(mesh, specs)

    if shape.kind == "train":
        ocfg = OptimizerConfig(
            state_dtype="bfloat16" if arch == "arctic-480b" else "float32")
        opt_shape = jax.eval_shape(
            lambda: init_optimizer(ocfg, params_shape))
        o_sh = shd.opt_shardings(mesh, p_sh)
        mb = microbatches or ARCH_MICROBATCHES.get(arch, TRAIN_MICROBATCHES)
        step = make_train_step(model, ocfg, microbatches=mb,
                               has_prefix=has_prefix_embeds(cfg))
        fn = jax.jit(step,
                     in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None),
                     donate_argnums=(0, 1))
        with jax.set_mesh(mesh):
            lowered = fn.lower(params_shape, opt_shape, specs)
    elif shape.kind == "prefill":
        max_len = shape.seq_len + prefix_len(cfg)
        cache_shape = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, max_len, jnp.bfloat16))
        c_sh = shd.cache_shardings(mesh, cache_shape, model.CACHE_BATCH_AXES)

        cap = moe_capacity if moe_capacity is not None else \
            (cfg.capacity_factor if cfg.num_experts else None)

        def prefill_step(params, tokens, cache, prefix_embeds=None):
            kw = {}
            if cfg.num_experts:
                kw["moe_capacity"] = cap
            logits, cache, _ = model.prefill(params, tokens, cache,
                                             prefix_embeds=prefix_embeds, **kw)
            # return only the last-position logits (sampling seed), not the
            # full (B, S, V) tensor
            return logits[:, -1], cache

        fn = jax.jit(prefill_step,
                     in_shardings=(p_sh, b_sh["tokens"], c_sh) +
                     ((b_sh["prefix_embeds"],) if "prefix_embeds" in specs else ()),
                     donate_argnums=(2,))
        with jax.set_mesh(mesh):
            args = [params_shape, specs["tokens"], cache_shape]
            if "prefix_embeds" in specs:
                args.append(specs["prefix_embeds"])
            lowered = fn.lower(*args)
    else:  # decode
        max_len = shape.seq_len + prefix_len(cfg)
        cdt = cache_dtype or jnp.bfloat16
        cache_shape = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, max_len, cdt))
        c_sh = shd.cache_shardings(mesh, cache_shape, model.CACHE_BATCH_AXES)
        specs = input_specs(cfg, shape, draft_window=draft_window)
        b_sh = shd.batch_shardings(mesh, specs)

        def serve_step(params, tokens, cache, pos):
            logits, cache = model.forward_window(params, tokens, cache, pos)
            return logits, cache

        fn = jax.jit(serve_step,
                     in_shardings=(p_sh, b_sh["tokens"], c_sh, b_sh["pos"]),
                     donate_argnums=(2,))
        with jax.set_mesh(mesh):
            lowered = fn.lower(params_shape, specs["tokens"], cache_shape,
                               specs["pos"])

    meta = {"arch": arch, "shape": shape_name,
            "mesh": "pod2x16x16" if multi_pod else "pod16x16",
            "chips": 512 if multi_pod else 256,
            "fsdp": fsdp, "microbatches": microbatches}
    return lowered, meta, cfg, shape


def probe_pair(cfg):
    """Two reduced-depth, scan-UNROLLED configs and the repeating-unit count.

    XLA's cost analysis counts a while-loop body once regardless of trip
    count, so scanned-layer programs under-report FLOPs/bytes/collectives.
    We therefore lower two shallow unrolled variants (n and n+1 repeating
    units), whose cost DIFFERENCE is the exact per-unit cost, and scale:

        cost_full = cost(n) + (cost(n+1) - cost(n)) * (units - n_units(n))

    This is exact for homogeneous stacks (all assigned archs) and keeps probe
    compile times low.
    """
    if cfg.family == "hybrid":
        g = cfg.hybrid_group
        return (cfg.replace(num_layers=g, scan_unroll=True),
                cfg.replace(num_layers=2 * g, scan_unroll=True),
                cfg.num_layers // g)
    if cfg.family == "audio":
        return (cfg.replace(num_layers=1, num_encoder_layers=1, scan_unroll=True),
                cfg.replace(num_layers=2, num_encoder_layers=2, scan_unroll=True),
                cfg.num_layers)
    if cfg.num_experts and cfg.first_k_dense:
        fkd = cfg.first_k_dense
        return (cfg.replace(num_layers=fkd + 1, scan_unroll=True),
                cfg.replace(num_layers=fkd + 2, scan_unroll=True),
                cfg.num_layers - fkd)
    return (cfg.replace(num_layers=1, scan_unroll=True),
            cfg.replace(num_layers=2, scan_unroll=True),
            cfg.num_layers)


def _cell_costs(lowered) -> dict:
    compiled = lowered.compile()
    cost = dict(compiled.cost_analysis() or {})
    from repro.roofline.analysis import parse_collectives
    coll = parse_collectives(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": float(coll.total_bytes),
        "collective_counts": coll.counts,
    }


def probe_costs(arch: str, shape_name: str, multi_pod: bool,
                microbatches: int | None = None, fsdp: bool = True,
                moe_capacity: float | None = None) -> dict:
    """Scan-corrected per-device costs for the full-depth program."""
    cfg_full = get_config(arch)
    c1_cfg, c2_cfg, units = probe_pair(cfg_full)

    def lower_with(cfg_probe):
        import repro.configs.base as cb
        # temporarily register the probe config under the arch name
        orig = cb._REGISTRY[arch]
        cb._REGISTRY[arch] = cfg_probe
        try:
            lowered, *_ = lower_cell(arch, shape_name, multi_pod,
                                     microbatches=1, fsdp=fsdp,
                                     moe_capacity=moe_capacity)
        finally:
            cb._REGISTRY[arch] = orig
        return lowered

    c1 = _cell_costs(lower_with(c1_cfg))
    c2 = _cell_costs(lower_with(c2_cfg))
    scale = units - 1  # c2 has exactly one more repeating unit than c1
    out = {}
    for key in ("flops", "bytes", "collective_bytes"):
        delta = c2[key] - c1[key]
        out[key] = c1[key] + delta * scale
        out[f"{key}_per_unit"] = delta
    out["collective_counts"] = {
        op: c1["collective_counts"][op]
        + (c2["collective_counts"][op] - c1["collective_counts"][op]) * scale
        for op in c1["collective_counts"]
    }
    out["units"] = units
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, force: bool = False,
             **kw) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    os.makedirs(RESULTS_DIR, exist_ok=True)
    out_path = os.path.join(RESULTS_DIR, f"{arch}__{shape_name}__{mesh_name}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    t0 = time.time()
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "status": "error"}
    try:
        lowered, meta, cfg, shape = lower_cell(arch, shape_name, multi_pod, **kw)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        cost = dict(cost) if cost else {}
        total, active = count_params(get_config(arch))
        mflops = model_flops(cfg, shape, total, active)

        # HLO collective inventory (structural cross-check: while-loop bodies
        # appear once — see EXPERIMENTS.md §Methodology)
        from repro.roofline.analysis import parse_collectives
        hlo_coll = parse_collectives(compiled.as_text())

        # analytic roofline terms (exact closed forms; the CPU backend's
        # cost_analysis over SPMD modules is unstable — evidence kept below)
        from repro.roofline.analytic import MeshInfo, roofline_terms, summarize
        mesh_info = MeshInfo(chips=meta["chips"],
                             dp=meta["chips"] // 16, mp=16)
        tb = roofline_terms(cfg, shape, mesh_info,
                            flash=kw.get("flash", False),
                            microbatches=kw.get("microbatches")
                            or ARCH_MICROBATCHES.get(arch, TRAIN_MICROBATCHES),
                            fsdp=kw.get("fsdp", True))
        rf = summarize(tb, mflops, meta["chips"])
        rf.update(arch=arch, shape=shape_name, mesh=mesh_name,
                  chips=meta["chips"])

        result = {
            **meta,
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "params_total": total,
            "params_active": active,
            "memory_analysis": _mem_dict(mem),
            "cost_flops_scanned_raw": cost.get("flops"),
            "cost_bytes_scanned_raw": cost.get("bytes accessed"),
            "hlo_collective_counts_per_scan_body": hlo_coll.counts,
            "hlo_collective_bytes_per_scan_body": hlo_coll.bytes_by_op,
            "roofline": rf,
        }
    except Exception as e:  # noqa: BLE001 — a failing cell is a recorded bug
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-2000:]
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2, default=str)
    return result


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    out = {}
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        if hasattr(mem, attr):
            out[attr] = int(getattr(mem, attr))
    return out


def all_cells():
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape_name in applicable_shapes(cfg):
            for multi_pod in (False, True):
                yield arch, shape_name, multi_pod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    if args.all:
        cells = list(all_cells())
    else:
        archs = [args.arch] if args.arch else ASSIGNED_ARCHS
        meshes = {"single": [False], "multi": [True],
                  "both": [False, True]}[args.mesh]
        cells = []
        for arch in archs:
            shapes = ([args.shape] if args.shape
                      else applicable_shapes(get_config(arch)))
            for s in shapes:
                for m in meshes:
                    cells.append((arch, s, m))

    n_ok = 0
    for arch, shape_name, multi_pod in cells:
        res = run_cell(arch, shape_name, multi_pod, force=args.force)
        ok = res.get("status") == "ok"
        n_ok += ok
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
        if ok:
            r = res["roofline"]
            print(f"[OK ] {arch:22s} {shape_name:12s} {mesh_name:10s} "
                  f"compile={res['compile_s']:>6.1f}s "
                  f"bottleneck={r['bottleneck']:10s} "
                  f"frac={r['peak_fraction']:.3f} "
                  f"terms(c/m/n)={r['compute_s']:.2e}/{r['memory_s']:.2e}/"
                  f"{r['collective_s']:.2e}")
            mem = res["memory_analysis"]
            print(f"      temp={mem.get('temp_size_in_bytes', 0)/1e9:.1f}GB "
                  f"args={mem.get('argument_size_in_bytes', 0)/1e9:.2f}GB")
        else:
            print(f"[ERR] {arch:22s} {shape_name:12s} {mesh_name:10s} "
                  f"{res.get('error', '?')}")
    print(f"\n{n_ok}/{len(cells)} cells OK")


if __name__ == "__main__":
    main()
