"""Observability subsystem: span tracer semantics, spans on the profiler's
clock, recorded intervals and JAX's compile events, kernel dispatch spans,
cell/engine span nesting (rounds and admission), the gateway's queue and
``/v1/trace`` endpoint, and the BENCH regression gate.

Async tests run through ``asyncio.run`` inside sync test functions (no
pytest-asyncio dependency).
"""

import asyncio
import json
import re
import threading

import pytest

from repro.api import CellConfig, MultiSpinCell, Request
from repro.obs import trace
from repro.serving.gateway import (
    GatewayClient,
    GatewayConfig,
    GatewayError,
    MultiSpinGateway,
)


def _cell(seed=0, max_batch=8, **kw):
    cfg = CellConfig(scheme="hete", max_batch=max_batch, seed=seed,
                     t_ver_fix=0.035, t_ver_lin=0.0177, L_max=8, **kw)
    return MultiSpinCell(cfg)


async def _start(cell, **gw_kw):
    gw = MultiSpinGateway(cell, GatewayConfig(port=0, idle_wait_s=0.02,
                                              **gw_kw))
    await gw.start()
    return gw, GatewayClient(port=gw.port)


# ---------------------------------------------------------------------------
# disabled tracing is free: the shared null singleton
# ---------------------------------------------------------------------------

def test_disabled_tracing_returns_the_null_singleton():
    """With no tracer installed every span() call returns the SAME object
    (the module singleton) — no per-call allocation — and the args-dict
    guard in the kernel dispatch helper short-circuits too."""
    assert trace.active() is None
    sp = trace.span("anything", cat="x", args={"k": 1})
    assert sp is trace.NULL_SPAN
    assert trace.span("other") is sp          # identity, not equality
    with sp as inner:                          # usable as a context manager
        inner.set(a=1)

    import jax.numpy as jnp

    from repro.kernels import ops
    assert ops._span("ops.x", jnp.zeros((2, 2))) is trace.NULL_SPAN


def test_tracing_scope_restores_previous_state():
    assert trace.active() is None
    with trace.tracing() as tr:
        assert trace.active() is tr
        with trace.tracing() as inner:
            assert trace.active() is inner
        assert trace.active() is tr
    assert trace.active() is None


# ---------------------------------------------------------------------------
# nesting, args, thread isolation, ring bound
# ---------------------------------------------------------------------------

def test_nested_spans_record_parent_links():
    with trace.tracing() as tr:
        with trace.span("outer", cat="t", args={"a": 1}) as outer:
            with trace.span("inner", cat="t") as inner:
                pass
            outer.set(b=2)
        with trace.span("sibling") as sib:
            pass
    spans = {sp.name: sp for sp in tr.snapshot()}
    assert set(spans) == {"outer", "inner", "sibling"}
    assert spans["inner"].parent_sid == spans["outer"].sid
    assert spans["outer"].parent_sid == -1
    assert spans["sibling"].parent_sid == -1
    assert len({sp.sid for sp in spans.values()}) == 3
    assert all(sp.dur_ns >= 0 for sp in spans.values())
    assert spans["outer"].args == {"a": 1, "b": 2}
    # exit order: inner closes before outer
    assert [sp.name for sp in tr.snapshot()] == ["inner", "outer", "sibling"]


def test_thread_local_stacks_never_cross_parent_links():
    """Each thread keeps its own span stack: a child's parent is always a
    span opened on the SAME thread, even under concurrent nesting."""
    tracer = trace.Tracer()
    n_threads, n_iter = 4, 25
    barrier = threading.Barrier(n_threads)

    def work(idx):
        barrier.wait()
        for _ in range(n_iter):
            with tracer.span(f"outer-{idx}"):
                with tracer.span(f"inner-{idx}"):
                    pass

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    spans = tracer.snapshot()
    assert len(spans) == n_threads * n_iter * 2
    by_sid = {sp.sid: sp for sp in spans}
    for sp in spans:
        if not sp.name.startswith("inner-"):
            continue
        parent = by_sid[sp.parent_sid]
        assert parent.tid == sp.tid
        assert parent.name == sp.name.replace("inner", "outer")


def test_ring_is_bounded_and_counts_drops():
    tracer = trace.Tracer(capacity=8)
    with trace.tracing(tracer):
        for i in range(20):
            with trace.span(f"s{i}"):
                pass
    spans = tracer.snapshot()
    assert len(spans) == 8
    assert [sp.name for sp in spans] == [f"s{i}" for i in range(12, 20)]
    assert tracer.dropped == 12
    assert tracer.export_chrome_trace()["otherData"]["dropped_spans"] == 12
    tracer.clear()
    assert tracer.snapshot() == [] and tracer.dropped == 0


def test_totals_aggregate_matches_snapshot():
    with trace.tracing() as tr:
        for _ in range(3):
            with trace.span("a"):
                pass
        for _ in range(2):
            with trace.span("b"):
                pass
    totals = tr.totals()
    assert totals["a"]["count"] == 3 and totals["b"]["count"] == 2
    want = sum(sp.dur_ns for sp in tr.snapshot() if sp.name == "a") * 1e-9
    assert totals["a"]["seconds"] == pytest.approx(want)


# ---------------------------------------------------------------------------
# the profiler's clock, recorded intervals, JAX's compile events
# ---------------------------------------------------------------------------

def test_profiler_spans_open_and_close_annotations_in_nesting_order(
        monkeypatch):
    import jax

    events = []

    class FakeAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append(("open", self.name))
            return self

        def __exit__(self, *exc):
            events.append(("close", self.name))
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    with trace.tracing(trace.Tracer(profiler=True)) as tr:
        with trace.span("outer"):
            with trace.span("inner"):
                pass
        with trace.span("after"):
            pass
    assert events == [("open", "obs:outer"), ("open", "obs:inner"),
                      ("close", "obs:inner"), ("close", "obs:outer"),
                      ("open", "obs:after"), ("close", "obs:after")]
    assert [sp.name for sp in tr.snapshot()] == ["inner", "outer", "after"]
    # a recorded span holds no annotation once closed
    assert all(sp._annotation is None for sp in tr.snapshot())


def test_profiler_spans_reach_a_real_profile(tmp_path):
    """Under an active profiler session the obs: spans land on the trace's
    host plane, as devtrace reads them."""
    import jax
    from jax.profiler import ProfileData

    with trace.tracing(trace.Tracer(profiler=True)):
        jax.profiler.start_trace(str(tmp_path))
        with trace.span("outer"):
            with trace.span("inner"):
                pass
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = {e.name for p in ProfileData.from_file(str(path)).planes
             if p.name.startswith("/host:") for ln in p.lines
             for e in ln.events}
    assert {"obs:outer", "obs:inner"} <= names


def test_record_exports_the_given_start_and_duration():
    tracer = trace.Tracer()
    t0 = tracer.t0_ns + 5_000_000            # 5 ms after tracer start
    with trace.tracing(tracer):
        with trace.span("open") as outer:
            inside = tracer.record("late", outer.t0_ns + 1000,
                                   outer.t0_ns + 3000, cat="q",
                                   args={"rid": 7})
            before = tracer.record("early", outer.t0_ns - 10,
                                   outer.t0_ns + 10)
        sp = tracer.record("queued", t0, t0 + 2_500_000, args={"rid": 3})
    assert (sp.t0_ns, sp.dur_ns, sp.parent_sid) == (t0, 2_500_000, -1)
    # filed under the innermost open span that began no later than it
    assert inside.parent_sid == outer.sid and before.parent_sid == -1
    ev = {e["name"]: e for e in tracer.export_chrome_trace()["traceEvents"]
          if e["ph"] == "X"}
    assert ev["queued"]["ts"] == pytest.approx(5000.0)
    assert ev["queued"]["dur"] == pytest.approx(2500.0)
    assert ev["queued"]["args"]["rid"] == 3
    assert ev["late"]["cat"] == "q" and ev["late"]["dur"] == pytest.approx(2.0)


def test_jax_compile_events_land_as_intervals_only_while_installed():
    import jax
    import jax.numpy as jnp

    def fresh_obs_fn(x):
        return jnp.cos(x) * 3.0 - 1.0

    with trace.tracing() as tr:
        with trace.span("caller") as caller:
            jax.jit(fresh_obs_fn)(jnp.ones(5)).block_until_ready()
    spans = {(sp.name, (sp.args or {}).get("fun_name")): sp
             for sp in tr.snapshot() if sp.cat == "jax"}
    assert ("jax.trace", "fresh_obs_fn") in spans
    assert ("jax.lower", "jit(fresh_obs_fn)") in spans
    assert ("jax.compile", "jit(fresh_obs_fn)") in spans
    for sp in spans.values():
        assert sp.parent_sid == caller.sid
        assert caller.t0_ns <= sp.t0_ns
        assert sp.t0_ns + sp.dur_ns <= caller.t0_ns + caller.dur_ns + 1_000_000
    assert tr.counters()["jax.compile"] >= 1

    # no tracer installed: the listener forwards nothing, anywhere
    idle = trace.Tracer()
    assert trace.active() is None

    def other_obs_fn(x):
        return jnp.tan(x) + 2.0

    jax.jit(other_obs_fn)(jnp.ones(5)).block_until_ready()
    assert idle.snapshot() == [] and idle.counters() == {}
    assert not any((sp.args or {}).get("fun_name", "").endswith(
        "other_obs_fn") for sp in tr.snapshot())


def test_cache_hits_are_counted():
    from jax import monitoring

    with trace.tracing() as tr:
        monitoring.record_event("/jax/compilation_cache/cache_hits")
        monitoring.record_event("/jax/some/other_event")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert tr.counters() == {"jax.cache_hit": 1}


def test_obs_stays_importable_without_jax():
    import subprocess
    import sys

    code = ("import sys; from repro.obs import trace; "
            "t = trace.install(trace.Tracer()); "
            "[trace.span('s').__enter__().__exit__()]; "
            "t.record('r', 0, 1); trace.uninstall(); "
            "assert 'jax' not in sys.modules, 'jax imported'")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr


# ---------------------------------------------------------------------------
# Chrome trace-event export
# ---------------------------------------------------------------------------

def test_chrome_trace_export_is_valid_trace_event_json():
    with trace.tracing() as tr:
        with trace.span("outer", cat="cell") as outer:
            with trace.span("inner", cat="kernel", args={"shape": [2, 2]}):
                pass
    text = tr.export_chrome_trace_json(process_name="test-proc")
    data = json.loads(text)                    # round-trips as strict JSON
    assert data["displayTimeUnit"] == "ms"
    events = data["traceEvents"]
    metas = [e for e in events if e["ph"] == "M"]
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["ph"] for e in events} == {"M", "X"}
    assert any(e["name"] == "process_name"
               and e["args"]["name"] == "test-proc" for e in metas)
    assert any(e["name"] == "thread_name" for e in metas)
    for e in xs:
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert e["pid"] == 1 and e["tid"] >= 1
        assert "sid" in e["args"]
    by_name = {e["name"]: e for e in xs}
    assert by_name["inner"]["args"]["parent_sid"] == \
        by_name["outer"]["args"]["sid"]
    assert by_name["inner"]["args"]["shape"] == [2, 2]
    assert outer.sid == by_name["outer"]["args"]["sid"]


# ---------------------------------------------------------------------------
# kernel dispatch spans (ops.*)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_ops_dispatch_emits_named_spans(monkeypatch, mode):
    """Every public op opens an ``ops.<name>`` span recording the backend
    actually dispatched plus the lead operand's shape/dtype."""
    monkeypatch.setenv("REPRO_KERNELS", mode)
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    B, S, H, KV, D = 2, 64, 4, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(keys[1], (B, S, KV, D), jnp.float32)
    v = jax.random.normal(keys[2], (B, S, KV, D), jnp.float32)
    with trace.tracing() as tr:
        out = ops.flash_attention(q, k, v)
    assert out.shape == q.shape
    spans = [sp for sp in tr.snapshot() if sp.name == "ops.flash_attention"]
    assert len(spans) == 1
    sp = spans[0]
    assert sp.cat == "kernel"
    assert sp.args["backend"] == mode
    assert sp.args["shape"] == [B, S, H, D]
    assert sp.args["dtype"] == "float32"


# ---------------------------------------------------------------------------
# cell instrumentation: step spans agree with summary()
# ---------------------------------------------------------------------------

def test_cell_step_spans_are_consistent_with_summary():
    cell = _cell(max_batch=4)
    for i, a in enumerate((0.71, 0.74, 0.86, 0.8)):
        cell.submit(Request(rid=i, prompt_len=8, max_new_tokens=16,
                            alpha=a, T_S=0.009))
    with trace.tracing() as tr:
        cell.run()
    spans = tr.snapshot()
    steps = [sp for sp in spans if sp.name == "cell.step"]
    assert len(steps) == len(cell.history) > 0
    for sp in steps:
        assert sp.args["scheme"] == "hete"
        assert sp.args["schedule"] == "sync"
        assert set(sp.args) >= {"round", "rids", "t_draft", "t_upload",
                                "t_ver", "t_round"}
    # the simulated phase seconds attached to spans ARE the summary numbers
    summary = cell.summary()
    assert sum(sp.args["t_draft"] for sp in steps) == \
        pytest.approx(summary["seconds_draft"])
    assert sum(sp.args["t_ver"] for sp in steps) == \
        pytest.approx(summary["seconds_verify"])
    # plan + verify spans nest under their round's step span
    step_sids = {sp.sid for sp in steps}
    for name in ("cell.plan", "cell.verify"):
        inner = [sp for sp in spans if sp.name == name]
        assert len(inner) == len(steps)
        assert all(sp.parent_sid in step_sids for sp in inner)


# ---------------------------------------------------------------------------
# gateway: /v1/trace + per-request trace ids
# ---------------------------------------------------------------------------

def test_gateway_trace_endpoint_and_stream_trace_ids():
    async def run():
        gw, cli = await _start(_cell(max_batch=2), trace_spans=True)
        ids = []
        async for ev in cli.stream_generate(prompt_len=8, max_new_tokens=8,
                                            alpha=0.8, T_S=0.009):
            assert "trace_id" in ev.data, ev.event
            ids.append(ev.data["trace_id"])
        data = await cli.trace()
        owned = gw._owns_tracer
        await gw.stop()
        return ids, data, owned

    ids, data, owned = asyncio.run(run())
    # queued/round/done all carry the SAME request-scoped trace id
    assert len(ids) >= 3 and len(set(ids)) == 1
    assert re.fullmatch(r"[0-9a-f]+-[0-9a-f]{12}", ids[0])
    # the exported trace is Chrome-trace shaped and contains the cell spans
    xs = [e for e in data["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in xs}
    assert {"cell.step", "cell.plan", "cell.verify"} <= names
    step_sids = {e["args"]["sid"] for e in xs if e["name"] == "cell.step"}
    assert all(e["args"]["parent_sid"] in step_sids
               for e in xs if e["name"] == "cell.verify")
    # the gateway owned the tracer and uninstalled it on stop
    assert owned and trace.active() is None


def test_gateway_trace_disabled_returns_409():
    async def run():
        gw, cli = await _start(_cell(max_batch=2))     # tracing off
        try:
            with pytest.raises(GatewayError) as exc:
                await cli.trace()
        finally:
            await gw.stop()
        return exc.value

    err = asyncio.run(run())
    assert err.status == 409
    assert err.body["error"] == "tracing_disabled"


def test_gateway_reuses_an_already_installed_tracer():
    """A test/bench scoped tracer survives the gateway: the gateway records
    into it and must NOT uninstall it on stop."""
    async def run(cell):
        gw, cli = await _start(cell, trace_spans=True)
        await cli.generate(prompt_len=8, max_new_tokens=8,
                           alpha=0.8, T_S=0.009)
        owned = gw._owns_tracer
        await gw.stop()
        return owned

    with trace.tracing() as tr:
        owned = asyncio.run(run(_cell(max_batch=2)))
        assert not owned
        assert trace.active() is tr
        assert any(sp.name == "cell.step" for sp in tr.snapshot())
    assert trace.active() is None


def test_gateway_request_records_its_queue_wait():
    """One gateway request: one gateway.queue interval carrying its rid,
    from the gateway's enqueue to its bind in cell.admit, plus the loop's
    emit and idle spans."""
    async def run(cell):
        gw, cli = await _start(cell, trace_spans=True)
        res = await cli.generate(prompt_len=8, max_new_tokens=8,
                                 alpha=0.8, T_S=0.009)
        spans = gw.tracer.snapshot()
        await gw.stop()
        return res, spans

    res, spans = asyncio.run(run(_cell(max_batch=2)))
    queue = [sp for sp in spans if sp.name == "gateway.queue"]
    assert len(queue) == 1
    assert queue[0].args == {"rid": res.rid}
    admits = [sp for sp in spans if sp.name == "cell.admit"
              and sp.args["n"] == 1]
    assert len(admits) == 1
    end = queue[0].t0_ns + queue[0].dur_ns
    assert admits[0].t0_ns <= end <= admits[0].t0_ns + admits[0].dur_ns
    names = {sp.name for sp in spans}
    assert {"gateway.emit", "gateway.idle", "cell.step"} <= names


# ---------------------------------------------------------------------------
# the full nesting chain on a REAL engine backend
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_engine_backend_nests_cell_engine_and_kernel_spans():
    """cell.step -> cell.verify -> engine.verify -> engine.* -> ops.* : the
    parent links walk all the way from a kernel dispatch span up to the
    round's cell.step span on a real paged SpecEngine."""
    import jax

    from repro.api import EngineBackend, SpecEngine
    from repro.configs import get_config

    tcfg = get_config("qwen2.5-3b").smoke()
    dcfg = tcfg.replace(num_layers=1, d_model=32, num_heads=2,
                        num_kv_heads=1, head_dim=16, d_ff=64,
                        name="draft-smoke")
    eng = SpecEngine(tcfg, dcfg, max_len=128, cache_kind="paged")
    eng.init_params(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 tcfg.vocab_size)
    backend = EngineBackend(eng, eng.start(prompts),
                            keep_finished_tokens=True)
    cell = MultiSpinCell(CellConfig(scheme="fixed", L_fixed=3, max_batch=2,
                                    seed=0), backend=backend)
    for i in range(2):
        cell.submit(Request(rid=i, prompt_len=8, max_new_tokens=8,
                            alpha=0.8, T_S=0.009))
    with trace.tracing() as tr:
        cell.run()

    spans = tr.snapshot()
    by_sid = {sp.sid: sp for sp in spans}
    names = {sp.name for sp in spans}
    assert {"cell.step", "cell.verify", "engine.verify"} <= names
    assert any(n.startswith("ops.") for n in names)

    def ancestors(sp):
        chain = []
        while sp.parent_sid >= 0:
            sp = by_sid[sp.parent_sid]
            chain.append(sp.name)
        return chain

    # every engine.verify span sits inside a cell.step
    for sp in spans:
        if sp.name == "engine.verify":
            assert "cell.step" in ancestors(sp)
    # and at least one kernel dispatch span has the FULL chain above it
    chains = [ancestors(sp) for sp in spans if sp.name.startswith("ops.")]
    assert any("engine.verify" in c and "cell.step" in c for c in chains)


def test_engine_admission_nests_its_spans_under_cell_admit():
    """An admission through a paged engine: cell.admit > engine.add_streams
    > {page_alloc, prompt_pad, two prefills, state_splice}, on one thread,
    with one splice fetch counted and each request's queue wait recorded
    from its submit to its bind."""
    import jax

    from repro.api import EngineBackend, SpecEngine
    from repro.configs import get_config

    tcfg = get_config("qwen2.5-3b").smoke()
    dcfg = tcfg.replace(num_layers=1, d_model=32, num_heads=2,
                        num_kv_heads=1, head_dim=16, d_ff=64,
                        name="draft-smoke")
    eng = SpecEngine(tcfg, dcfg, max_len=64, cache_kind="paged")
    eng.init_params(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                 tcfg.vocab_size)
    backend = EngineBackend(eng, eng.start(prompts))
    cell = MultiSpinCell(CellConfig(scheme="fixed", L_fixed=3, max_batch=2,
                                    seed=0), backend=backend)
    with trace.tracing() as tr:
        for i in range(2):      # the first takes the started row
            cell.submit(Request(rid=i, prompt_len=12, max_new_tokens=8,
                                alpha=0.8, T_S=0.009))
        assert len(cell.admit()) == 2

    spans = tr.snapshot()
    by_sid = {sp.sid: sp for sp in spans}
    (admit,) = [sp for sp in spans if sp.name == "cell.admit"]
    (add,) = [sp for sp in spans if sp.name == "engine.add_streams"]
    assert admit.args["n"] == 2
    assert (add.args["n"], add.args["M"], add.args["Mb"]) == (1, 12, 16)
    assert add.args["rows"] == [1]
    assert by_sid[add.parent_sid] is admit and admit.parent_sid == -1
    kids = sorted(sp.name for sp in spans if sp.parent_sid == add.sid)
    assert kids == ["engine.page_alloc", "engine.prefill", "engine.prefill",
                    "engine.prompt_pad", "engine.state_splice"]
    assert sorted(sp.args["model"] for sp in spans
                  if sp.name == "engine.prefill") == ["draft", "target"]
    for sp in spans:
        if sp.parent_sid == add.sid:
            assert sp.tid == add.tid
            assert add.t0_ns <= sp.t0_ns
            assert sp.t0_ns + sp.dur_ns <= add.t0_ns + add.dur_ns
    assert add.tid == admit.tid
    assert tr.counters()["engine.admit_host_sync"] == 1
    queue = sorted((sp.args["rid"], sp) for sp in spans
                   if sp.name == "gateway.queue")
    assert [rid for rid, _ in queue] == [0, 1]
    for _, sp in queue:
        assert sp.t0_ns < admit.t0_ns + admit.dur_ns and sp.dur_ns > 0
    # the second request waited through the first one's bind
    assert queue[1][1].dur_ns >= queue[0][1].dur_ns


# ---------------------------------------------------------------------------
# BENCH regression gate
# ---------------------------------------------------------------------------

def test_regression_gate_passes_on_committed_baselines(capsys):
    from benchmarks import regression
    assert regression.run() == 0
    assert "0 failure(s)" in capsys.readouterr().out


def test_regression_gate_fails_on_quality_regression(tmp_path):
    """A halved goodput in a fresh run must fail the gate even though the
    envelope hosts match (quality metrics always gate)."""
    import shutil

    from benchmarks import regression
    for fname in regression.BENCH_FILES:
        shutil.copy(str(regression.REPO_ROOT) + "/" + fname,
                    str(tmp_path / fname))
    churn = json.loads((tmp_path / "BENCH_churn.json").read_text())
    for row in churn["rows"]:
        if "goodput" in row:
            row["goodput"] *= 0.5
    (tmp_path / "BENCH_churn.json").write_text(json.dumps(churn))
    assert regression.run(fresh_dir=str(tmp_path)) > 0


def test_regression_gate_host_gating_for_timing_metrics(tmp_path):
    """Timing metrics gate same-host (or under --strict-timing) but only
    WARN cross-host; quality metrics are host-independent."""
    from benchmarks import regression

    def write(dirname, host, us):
        d = tmp_path / dirname
        d.mkdir(exist_ok=True)
        (d / "BENCH_kernels.json").write_text(json.dumps({
            "schema_version": 2, "host": host,
            "rows": [{"name": "kernels/x", "us_per_call": us}]}))
        return str(d)

    base = write("base", "host-a", 10.0)
    slow_other_host = write("other", "host-b", 100.0)
    slow_same_host = write("same", "host-a", 100.0)
    files = ("BENCH_kernels.json",)
    # cross-host 10x slowdown: informational only
    assert regression.run(base, slow_other_host, files=files) == 0
    # ... unless forced
    assert regression.run(base, slow_other_host, strict_timing=True,
                          files=files) > 0
    # same host: gates without any flag
    assert regression.run(base, slow_same_host, files=files) > 0


def test_regression_gate_fails_on_missing_rows_and_metrics(tmp_path):
    from benchmarks import regression

    def write(dirname, rows):
        d = tmp_path / dirname
        d.mkdir(exist_ok=True)
        (d / "BENCH_kernels.json").write_text(json.dumps({
            "schema_version": 2, "host": "h", "rows": rows}))
        return str(d)

    base = write("base", [{"name": "kernels/x", "goodput": 1.0}])
    files = ("BENCH_kernels.json",)
    # a metric that vanishes from the fresh rows is a failure
    no_metric = write("nm", [{"name": "kernels/x"}])
    assert regression.run(base, no_metric, files=files) > 0
    # a whole row that vanishes is a failure
    no_row = write("nr", [{"name": "kernels/y", "goodput": 1.0}])
    assert regression.run(base, no_row, files=files) > 0
    # a missing fresh file is a failure
    empty = tmp_path / "empty"
    empty.mkdir()
    assert regression.run(base, str(empty), files=files) > 0
