"""One benchmark run: set-up, the measured window, the readings, the check.

Everything a run needs is found by name from ``BENCHMARK.json``: the cell
names a configuration (``configs``: a file of sizes with its reference) and
a traffic mix (``bench/traffic/<traffic>.json``); each metric is a module
``bench/metrics/<name>.py`` with a ``read(run)`` function.  The last line
of standard output is the result object; the numbers that decided
``correct`` close standard error, each beside its limit.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import importlib
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPAN_CAPACITY = 1 << 18     # the program's spans kept in a traced run


class NoChip(SystemExit):
    pass


def parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(items, name, what):
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(spec: dict, workload: str, root: Path = ROOT):
    wl = find(spec["workloads"], workload, "workload")
    cfg_entry = find(spec["configs"], wl["config"], "config")
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{wl['traffic']}.json")
                         .read_text())
    return wl, config, traffic


def metric_entries(spec: dict, workload: str, trace: bool) -> list:
    """The cell's metrics for this kind of run: end-to-end without the
    profiler, per-layer with it."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def require_chip(chips: int, allow_cpu: bool):
    import jax

    devs = jax.devices()
    if not allow_cpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform} "
                     f"({devs[0].device_kind}); the benchmark never runs "
                     "on the CPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs[:chips]


def task_ranges(config: dict) -> list:
    acc = config["acceptance"]
    V = config["target"]["vocab_size"]
    m, n_tasks = int(acc["cluster_size"]), len(acc["task_sigma"])
    per = (V // m) // n_tasks * m
    return [(i * per, (i + 1) * per) for i in range(n_tasks)]


class Run:
    """What metric readers read: the cell, its window and its records; in a
    traced run also the device trace's summary (``trace``), the program's
    spans (``spans``) and each of its counters' change over the window
    (``counters``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    # -- helpers shared by readers ------------------------------------

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def window_outcomes(self):
        return [o for o in self.outcomes
                if self.t_open <= o.due < self.t_close]

    def window_steps(self):
        return [s for s in self.recorder.steps
                if self.t_open <= s.t0 < self.t_close
                and s.record is not None]

    def window_rounds(self, t0=None, t1=None):
        t0 = self.t_open if t0 is None else t0
        t1 = self.t_close if t1 is None else t1
        return [r for r in self.recorder.rounds if t0 <= r.t0 < t1]

    def window_prefills(self, t0=None, t1=None):
        t0 = self.t_open if t0 is None else t0
        t1 = self.t_close if t1 is None else t1
        return [p for p in self.recorder.prefills if t0 <= p[0] < t1]

    def spans(self, name: str, t0=None, t1=None) -> list:
        """(start, end) in seconds, on the clock the window is timed by, of
        the program's finished spans called ``name`` that start in the
        window (or in [t0, t1)); empty in an untraced run."""
        t0 = self.t_open if t0 is None else t0
        t1 = self.t_close if t1 is None else t1
        return [(a, b) for n, a, b in self.span_log
                if n == name and t0 <= a < t1]


def percentile(values, q: float) -> float:
    vals = np.asarray(values, np.float64)
    return float(np.percentile(vals, q)) if len(vals) else math.nan


async def drive(served, traffic, table, seconds: float, trace_dir,
                seed: int, log, tracer=None):
    """Ramp up, measure, and close; returns the window's facts, with the
    change of each of ``tracer``'s counters over the window."""
    import jax

    from loadgen import LoadGen
    from repro.serving.gateway import GatewayConfig, MultiSpinGateway

    gw = MultiSpinGateway(served.cell, GatewayConfig(port=0,
                                                     idle_wait_s=0.002))
    await gw.start()
    lg = LoadGen(traffic, table, "127.0.0.1", gw.port, seed)
    lg.start()
    # ramp-up: the window opens once max_batch requests stream tokens
    t_ramp = time.perf_counter()
    while sum(o.first_t is not None for o in lg.outcomes) < served.max_batch:
        if gw.round_error is not None:
            raise RuntimeError(f"a round failed during ramp-up: "
                               f"{gw.round_error!r}")
        if time.perf_counter() - t_ramp > 600:
            raise RuntimeError("ramp-up did not fill the batch in 600 s")
        await asyncio.sleep(0.005)
    t_open = time.perf_counter()
    counters_open = tracer.counters() if tracer is not None else {}
    trace = None
    if trace_dir is not None:
        # a steady part of the window: its middle, at most 4 s
        span = min(4.0, 0.5 * seconds)
        await asyncio.sleep(0.5 * (seconds - span))
        jax.profiler.start_trace(trace_dir)
        ann = jax.profiler.TraceAnnotation("bench.window")
        ann.__enter__()
        t_trace0 = time.perf_counter()
        await asyncio.sleep(span)
        t_trace1 = time.perf_counter()
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        trace = (t_trace0, t_trace1)
        await asyncio.sleep(max(0.0, t_open + seconds - time.perf_counter()))
    else:
        await asyncio.sleep(seconds)
    t_close = time.perf_counter()
    counters = {}
    if tracer is not None:
        counters = {k: v - counters_open.get(k, 0)
                    for k, v in tracer.counters().items()}
    lg.stop_sending()
    # latencies of requests sent in the window: wait (a minute at most)
    # for each one's first token
    t_wait = time.perf_counter()
    while any(o.first_t is None and o.error is None
              for o in lg.outcomes if o.due < t_close):
        if time.perf_counter() - t_wait > 60 or gw.round_error is not None:
            break
        await asyncio.sleep(0.01)
    # requests still streaming are cut by closing their connections (the
    # gateway retires them as client disconnects) before the gateway stops
    await lg.cut()
    await gw.stop()
    if gw.round_error is not None:
        log(f"round error: {gw.round_error!r}")
    return dict(t_open=t_open, t_close=t_close, trace=trace,
                outcomes=lg.outcomes, round_error=gw.round_error,
                counters=counters)


def judge(readings: dict, limits: dict) -> bool:
    """The comparison that decides ``correct``: every number that the
    configuration limits was read and lies at or under its limit."""
    return all(readings.get(k) is not None and readings[k] <= lim
               for k, lim in limits.items())


def sample_requests(outcomes, seed: int, n: int, t_close: float):
    """Finished requests to check: the one with most served tokens, then
    others drawn from the seed."""
    done = [o for o in outcomes if o.done_t is not None and o.error is None
            and o.done_t <= t_close + 60]
    if not done:
        return []
    done.sort(key=lambda o: (-len(o.tokens), o.req.index))
    rest = done[1:]
    rng = np.random.default_rng([int(seed) % (2 ** 32), 11])
    pick = [done[0]] + [rest[i] for i in
                        sorted(rng.permutation(len(rest))[:n - 1])]
    return pick


def run(argv=None, *, allow_cpu: bool = False, cell=None,
        hooks: dict | None = None, t_start: float | None = None) -> dict:
    """One run; prints its lines and returns the result object.
    ``allow_cpu``, ``cell`` (a (spec, workload, config, traffic) tuple in
    place of the files) and ``hooks`` serve the CPU rehearsal and the
    fault checks only: the command line never sets them."""
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    hooks = hooks or {}
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    if cell is None:
        spec = load_spec()
        wl, config, traffic = load_cell(spec, args.workload)
    else:
        spec, wl, config, traffic = cell
    devices = require_chip(int(wl["chips"]), allow_cpu)
    dev = devices[0]

    import jax

    from repro.serving.compiled import setup_compilation_cache

    sys.path.insert(0, str(BENCH))
    import compare
    import loadgen
    from recorder import Recorder
    from servecell import ServedCell

    if not _COMPILES_ON:
        jax.monitoring.register_event_duration_secs_listener(_count_compiles)
        _COMPILES_ON.append(True)
    cache_dir = setup_compilation_cache()
    log(f"compilation cache: {cache_dir}")
    table = loadgen.build_table(traffic, args.seed,
                                config["target"]["vocab_size"],
                                task_ranges(config))
    t0 = time.perf_counter()
    served = ServedCell(config, args.seed)
    log(f"set-up: cell and weights {time.perf_counter() - t0:.3f} s "
        f"(process start + {t0 - t_start:.3f} s)")
    if "served" in hooks:
        hooks["served"](served)
    t0 = time.perf_counter()
    served.warm([len(r.prompt)
                 for r in table[:int(traffic["warm_requests"])]])
    log(f"set-up: warm-up {time.perf_counter() - t0:.3f} s, "
        f"{len(compiles_since(t0))} programs compiled or loaded")
    recorder = Recorder(served, annotate=bool(args.trace))
    trace_dir = tracer = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        # the program's own spans and counters, for the per-layer readers;
        # on the host clock only (no profiler annotations)
        from repro.obs import trace as obs_trace
        tracer = obs_trace.install(obs_trace.Tracer(capacity=SPAN_CAPACITY))
    try:
        facts = asyncio.run(drive(served, traffic, table, args.seconds,
                                  trace_dir, args.seed, log, tracer))
    finally:
        if tracer is not None:
            obs_trace.uninstall()
    span_log = []
    if tracer is not None:
        span_log = [(sp.name, sp.t0_ns * 1e-9, (sp.t0_ns + sp.dur_ns) * 1e-9)
                    for sp in tracer.snapshot()]
        log(f"program spans kept {len(span_log)}, dropped {tracer.dropped}; "
            f"counters over the window {json.dumps(facts['counters'])}")
    t_open, t_close = facts["t_open"], facts["t_close"]
    compiles = [c for c in _COMPILES if t_open <= c[0] < t_close]
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    recorder.fetch()
    outcomes = facts["outcomes"]
    sent = [o for o in outcomes if t_open <= o.due < t_close]
    failed = [o for o in sent if o.error not in (None, "gateway_shutdown")]
    lateness = [o.sent - o.due for o in sent]
    log(f"window: {t_close - t_open:.3f} s, requests sent {len(sent)}, "
        f"failed {len(failed)}, rounds "
        f"{sum(1 for r in recorder.rounds if t_open <= r.t0 < t_close)}, "
        f"programs compiled or loaded in window {len(compiles)} taking "
        f"{sum(c[2] for c in compiles):.3f} s "
        f"{sorted({c[1] for c in compiles})[:8]}, generator late by "
        f"max {max(lateness, default=0):.6f} s "
        f"p95 {percentile(lateness, 95):.6f} s, peak_bytes_in_use {peak}")
    if failed:
        log(f"failed requests: {sorted({o.error for o in failed})}")

    summary = None
    if trace_dir is not None:
        if "trace" in hooks:
            hooks["trace"](trace_dir)
        import devtrace
        summary = devtrace.reduce(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Run(spec=spec, workload=wl, config=config, traffic=traffic,
              recorder=recorder, outcomes=outcomes,
              t_start=t_start, t_open=t_open, t_close=t_close,
              trace_window=facts["trace"], trace=summary,
              span_log=span_log, counters=facts["counters"],
              device_kind=dev.device_kind, n_devices=len(devices))
    metrics = {}
    for m in metric_entries(spec, wl["name"], bool(args.trace)):
        mod = importlib.import_module(f"metrics.{m['name']}")
        for attr in ("LAYER", "SOURCE", "MOVES"):
            key = attr.lower()
            if key in m and getattr(mod, attr) != m[key]:
                raise ValueError(f"metrics/{m['name']}.py says {attr} "
                                 f"{getattr(mod, attr)!r}, BENCHMARK.json "
                                 f"{m[key]!r}")
        try:
            value = mod.read(ctx)
        except KeyError as e:
            if not allow_cpu:
                raise
            log(f"metric {m['name']}: not on this device ({e})")
            continue
        if value is None:
            log(f"metric {m['name']}: nothing to read")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        note = getattr(mod, "note", None)
        if note is not None:
            log(f"metric {m['name']}: {note(ctx)}")
    per_task = accept_by_task(ctx)
    log(f"acceptance per drafted token by task: {json.dumps(per_task)}")

    # -- the check: after the window, with the program's state freed ----
    n_check = int(traffic["check_requests"])
    picks = sample_requests(outcomes, args.seed, n_check, t_close)
    samples = [(o.rid, o.req.prompt, o.tokens) for o in picks]
    rounds = list(recorder.rounds)
    served.free()
    del served, ctx, recorder
    gc.collect()
    T_max = int(traffic["check_len"])
    limits = config["limits"]
    if samples:
        got = compare.check(config, args.seed, samples, rounds, T_max,
                            hooks.get("precisions", ("program",)))
        readings = got["program"]
        log(f"checked: {json.dumps(got['counts'])}")
        if "readings" in hooks:
            hooks["readings"](got)
    else:
        log("checked: no finished request to check")
        readings = {k: None for k in compare.NUMBERS}
    correct = (bool(samples) and not failed
               and facts["round_error"] is None and judge(readings, limits))
    checks = {k: {"value": readings[k], "limit": lim}
              for k, lim in limits.items()}
    checks["failed_requests"] = {"value": len(failed), "limit": 0}
    for k in compare.NUMBERS:
        if k not in limits:
            log(f"read, not compared: {k} {readings.get(k)!r}")
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} limit {v['limit']!r}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(sent),
              "failed": len(failed), "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return result


def compiles_since(since: float) -> list:
    return [c for c in _COMPILES if c[0] >= since]


_COMPILES: list = []
_COMPILES_ON: list = []


def _count_compiles(event, duration, **kw):
    # every program JAX compiles or loads from the persistent cache
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES.append((time.perf_counter(), kw.get("fun_name", "?"),
                          duration))


def accept_by_task(ctx) -> dict:
    """Accepted drafted tokens over drafted tokens tested, per task, over
    the window's rounds (from the round records and the requests' tasks)."""
    task_of = {o.rid: o.req.task for o in ctx.outcomes if o.rid is not None}
    acc, tested = {}, {}
    for r in ctx.window_rounds():
        for b, (rid, _) in r.live.items():
            t = task_of.get(rid)
            if t is None:
                continue
            n = int(r.out["accept_counts"][b])
            Lk = int(r.lengths[b])
            acc[t] = acc.get(t, 0) + n
            tested[t] = tested.get(t, 0) + min(n + 1, Lk)
    return {t: round(acc[t] / tested[t], 4) for t in sorted(tested)
            if tested[t]}


def main(argv=None, t_start=None):
    try:
        run(argv, t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0
