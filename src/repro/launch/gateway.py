"""Multi-SPIN live serving gateway CLI.

  PYTHONPATH=src python -m repro.launch.gateway --port 8011         # synthetic
  PYTHONPATH=src python -m repro.launch.gateway --backend engine \
      --arch qwen2.5-3b --smoke-arch --scheme hete
  PYTHONPATH=src python -m repro.launch.gateway --backend engine \
      --arch qwen2.5-3b --draft-arch qwen3.5-0.8b --max-len 1024 \
      --compile jit+donate                       # full width, bf16, TPU

Stands up a ``MultiSpinCell`` and serves it live over HTTP/1.1 + SSE
(``POST /v1/generate`` streams committed tokens per round; ``GET /metrics``
is Prometheus; see ``repro.serving.gateway``).  The synthetic backend needs
no JAX and starts instantly; ``--backend engine`` builds a real paged
``SpecEngine`` and streams actual committed token ids.

``--smoke`` does not serve: it runs an in-process loadgen burst against the
configured cell and prints the report — the same path as
``benchmarks/bench_gateway.py`` — so the full client->server->cell loop can
be exercised from one command with no open port.
"""

from __future__ import annotations

import argparse
import asyncio
import json

from repro.core.schemes import (
    available_schemes,
    parse_scheme_args,
    scheme_help_text,
)
from repro.serving.cell import SCHEDULES


def build_cell(args):
    import numpy as np

    from repro.api import CellConfig, MultiSpinCell
    from repro.core.channel import ChannelConfig

    scheme_params = parse_scheme_args(args.scheme, args.scheme_arg)
    if args.backend == "synthetic":
        cfg = CellConfig(scheme=args.scheme, scheme_params=scheme_params,
                         schedule=args.schedule, max_batch=args.max_batch,
                         t_ver_fix=0.035, t_ver_lin=0.0177, L_max=args.L_max,
                         seed=args.seed)
        return MultiSpinCell(cfg)

    import jax
    import jax.numpy as jnp

    from repro.api import EngineBackend, SpecEngine
    from repro.configs import get_config

    tcfg = get_config(args.arch)
    if args.draft_arch:
        dcfg = get_config(args.draft_arch)
    else:
        dcfg = tcfg.smoke().replace(num_layers=1, d_model=64, num_heads=2,
                                    num_kv_heads=1, head_dim=32, d_ff=128,
                                    vocab_size=tcfg.vocab_size, name="draft")
    if args.smoke_arch:
        tcfg, cache_dtype = tcfg.smoke(), jnp.float32
        if args.draft_arch:
            dcfg = dcfg.smoke()
        dcfg = dcfg.replace(vocab_size=tcfg.vocab_size)
    else:
        # full width serves bf16 weights and a bf16 KV cache
        tcfg, dcfg = (c.replace(param_dtype="bfloat16",
                                compute_dtype="bfloat16")
                      for c in (tcfg, dcfg))
        cache_dtype = jnp.bfloat16
    engine = SpecEngine(tcfg, dcfg, max_len=args.max_len, cache_kind="paged",
                        num_pages=args.max_batch * 2 * (args.max_len // 16),
                        compile_mode=args.compile, cache_dtype=cache_dtype)
    engine.init_params(jax.random.PRNGKey(args.seed))
    prompts = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                 (args.max_batch, 8), 0, tcfg.vocab_size)
    state = engine.start(prompts)
    if args.compile != "eager":
        # pre-trace the jitted round steps so the first live requests do not
        # pay compiles; the cell dispatches full-batch rounds at the exact
        # draft depth the scheme picks, so warm (max_batch, L) per length.
        lengths = ([int(x) for x in args.warmup_lengths.split(",") if x]
                   if args.warmup_lengths else [args.L_max])
        state, info = engine.warmup(
            state, sorted({(args.max_batch, L) for L in lengths}))
        print(f"warmup: traced {len(info)} bucket(s) in "
              f"{sum(info.values()):.1f}s")
    backend = EngineBackend(engine, state, keep_finished_tokens=True)
    cfg = CellConfig(scheme=args.scheme, scheme_params=scheme_params,
                     schedule=args.schedule, max_batch=args.max_batch,
                     channel=ChannelConfig(vocab_size=tcfg.vocab_size),
                     t_ver_fix=0.035, t_ver_lin=0.0177, L_max=args.L_max,
                     seed=args.seed)
    return MultiSpinCell(cfg, backend=backend,
                         rng=np.random.default_rng(args.seed))


async def _serve(args):
    from repro.serving.gateway import GatewayConfig, MetricsHub, serve

    cell = build_cell(args)
    if args.profiler_port:
        # a profile captured from this port shows the obs: spans of the
        # tracer installed here over the device's operations
        import jax

        from repro.obs import trace

        jax.profiler.start_server(args.profiler_port)
        trace.install(trace.Tracer(profiler=True))
        print(f"  profiler server on port {args.profiler_port}")
    hub = MetricsHub(trace_path=args.trace)
    gcfg = GatewayConfig(host=args.host, port=args.port,
                         trace_spans=args.trace_spans)
    print(f"multi-spin gateway: scheme={args.scheme} backend={args.backend} "
          f"max_batch={args.max_batch}")
    print(f"  GET  http://{args.host}:{args.port}/              (dashboard)")
    print(f"  POST http://{args.host}:{args.port}/v1/generate   (SSE)")
    print(f"  GET  http://{args.host}:{args.port}/metrics       (Prometheus)")
    print(f"  GET  http://{args.host}:{args.port}/v1/stats      (JSON)")
    if args.trace_spans:
        print(f"  GET  http://{args.host}:{args.port}/v1/trace      "
              "(Perfetto JSON)")
    await serve(cell, config=gcfg, hub=hub)


async def _smoke(args):
    from repro.serving.gateway import (
        GatewayConfig,
        LoadGenConfig,
        MultiSpinGateway,
        run_loadgen,
    )

    cell = build_cell(args)
    gw = MultiSpinGateway(cell, GatewayConfig(port=0, idle_wait_s=0.02))
    await gw.start()
    try:
        report = await run_loadgen(
            "127.0.0.1", gw.port,
            LoadGenConfig(rate_per_s=32.0, n_requests=args.smoke_requests,
                          max_new_tokens_choices=(4, 8), seed=args.seed))
        from repro.serving.gateway import GatewayClient
        stats = await GatewayClient(port=gw.port).stats()
    finally:
        await gw.stop()
    report.pop("records", None)
    report["rounds_total"] = stats["rounds_total"]
    print(json.dumps(report, indent=2, sort_keys=True))
    if report["n_error"]:
        raise SystemExit(f"gateway smoke FAILED: {report['errors']}")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog=scheme_help_text())
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8011,
                    help="0 picks an ephemeral port")
    ap.add_argument("--backend", default="synthetic",
                    choices=("synthetic", "engine"))
    ap.add_argument("--arch", default="qwen2.5-3b",
                    help="target architecture (engine backend)")
    ap.add_argument("--draft-arch", default=None,
                    help="draft architecture by registry name (engine "
                         "backend; default: a one-layer draft derived from "
                         "the target's smoke config)")
    ap.add_argument("--smoke-arch", action="store_true",
                    help="shrink the engine archs to smoke scale (float32); "
                         "without it the engine serves bfloat16")
    ap.add_argument("--max-len", type=int, default=256,
                    help="engine stream length ceiling")
    ap.add_argument("--compile", default="eager",
                    choices=("eager", "jit", "jit+donate"),
                    help="engine round-path compile mode (engine backend)")
    ap.add_argument("--warmup-lengths", default="", metavar="L1,L2,...",
                    help="draft depths to pre-trace at startup when "
                         "--compile != eager (default: L-max only)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--scheme", default="hete", choices=available_schemes())
    ap.add_argument("--scheme-arg", action="append", default=[],
                    metavar="KEY=VAL",
                    help="scheme parameter (repeatable); valid keys below")
    ap.add_argument("--schedule", default="sync", choices=SCHEDULES)
    ap.add_argument("--L-max", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="append per-round RoundMetrics JSONL here")
    ap.add_argument("--trace-spans", action="store_true",
                    help="install the span tracer; GET /v1/trace serves "
                         "Chrome trace-event JSON for Perfetto")
    ap.add_argument("--profiler-port", type=int, default=0, metavar="N",
                    help="start a JAX profiler server on port N and install "
                         "a span tracer whose spans land in its traces as "
                         "obs:<name>, beside the device's operations")
    ap.add_argument("--smoke", action="store_true",
                    help="no server: in-process loadgen burst, print report")
    ap.add_argument("--smoke-requests", type=int, default=8)
    return ap


def main():
    args = build_parser().parse_args()
    if args.backend == "engine":
        from repro.serving.compiled import setup_compilation_cache
        setup_compilation_cache()
    try:
        asyncio.run(_smoke(args) if args.smoke else _serve(args))
    except KeyboardInterrupt:
        print("\ngateway stopped")


if __name__ == "__main__":
    main()
