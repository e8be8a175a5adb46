"""Live serving gateway: SSE streaming vs batch bit-identity, telemetry,
disconnect/retire lifecycle on the paged engine, and loadgen math.

Async tests run through ``asyncio.run`` inside sync test functions (no
pytest-asyncio dependency).
"""

import asyncio
import json
import re

import numpy as np
import pytest

from repro.api import CellConfig, MultiSpinCell, Request
from repro.serving.gateway import (
    GatewayClient,
    GatewayConfig,
    GatewayError,
    MetricsHub,
    MultiSpinGateway,
    percentile,
    summarize,
)

REQ_FIELDS = [dict(prompt_len=8, max_new_tokens=16, alpha=a, T_S=0.009)
              for a in (0.71, 0.74, 0.86, 0.8, 0.71, 0.74, 0.86, 0.8)]


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
# the acceptance test: >= 8 concurrent SSE clients, bit-identical to batch
# ---------------------------------------------------------------------------

def test_concurrent_sse_clients_bit_identical_to_batch():
    """8 concurrent SSE clients against a live gateway produce EXACTLY the
    round sequence and per-request token counts of ``cell.run()`` on an
    identically-seeded batch cell: same seed + same submission order +
    first-step barrier => same rng stream => same plans/draws/rounds."""

    async def live():
        gw, cli = await _start(_cell(), step_barrier=len(REQ_FIELDS))
        # submit sequentially — each client waits for its `queued` event so
        # rid assignment (and cell submission order) is deterministic
        streams = []
        for f in REQ_FIELDS:
            gen = cli.stream_generate(**f)
            ev = await gen.__anext__()
            assert ev.event == "queued"
            streams.append((ev.data["rid"], gen))
        assert [rid for rid, _ in streams] == list(range(len(REQ_FIELDS)))

        async def collect(rid, gen):
            toks, per_round, done = [], [], False
            async for ev in gen:
                if ev.event == "round":
                    toks.extend(ev.data["tokens"])
                    per_round.append(ev.data["n"])
                elif ev.event == "done":
                    done = True
            await gen.aclose()
            return rid, toks, per_round, done
        results = await asyncio.gather(
            *(collect(rid, gen) for rid, gen in streams))
        history = list(gw.cell.history)
        stats = gw.cell.scheduler.stats
        await gw.stop()
        return results, history, stats

    results, live_history, live_stats = asyncio.run(live())
    assert all(done for _, _, _, done in results)

    batch = _cell()
    reqs = [Request(rid=i, **f) for i, f in enumerate(REQ_FIELDS)]
    for r in reqs:
        batch.submit(r)
    batch.run()

    # identical round-by-round protocol execution
    assert len(live_history) == len(batch.history)
    for a, b in zip(live_history, batch.history):
        np.testing.assert_array_equal(a.lengths, b.lengths)
        np.testing.assert_array_equal(a.accepted, b.accepted)
        np.testing.assert_array_equal(a.rids, b.rids)
        assert a.t_round == b.t_round
        assert a.draft_width == b.draft_width
    # identical per-request outcomes; streamed counts respect the cap
    by_rid = {r.rid: r for r in reqs}
    for rid, toks, per_round, _ in results:
        assert len(toks) == by_rid[rid].generated == 16
        assert per_round == [n for n in per_round if n > 0]
    assert live_stats.total_tokens == batch.scheduler.stats.total_tokens
    assert live_stats.completed == len(REQ_FIELDS)


# ---------------------------------------------------------------------------
# /metrics + /v1/stats
# ---------------------------------------------------------------------------

def test_metrics_scrape_parses_and_reports_required_families():
    async def run():
        gw, cli = await _start(_cell(max_batch=4))
        rs = await asyncio.gather(
            *(cli.generate(prompt_len=8, max_new_tokens=8, alpha=0.8,
                           T_S=0.009) for _ in range(4)))
        text = await cli.metrics()
        stats = await cli.stats()
        await gw.stop()
        return rs, text, stats

    rs, text, stats = asyncio.run(run())
    assert all(r.done for r in rs)

    # every exposition line parses as comment or `name{labels} value`
    line_re = re.compile(r"^(#.*|[a-z_]+(\{[^}]*\})? [0-9.eE+-]+)$")
    for line in text.strip().splitlines():
        assert line_re.match(line), f"unparseable metrics line: {line!r}"

    def value(name):
        m = re.search(rf"^{name} ([0-9.eE+-]+)$", text, re.M)
        assert m, f"metric {name} missing"
        return float(m.group(1))

    assert value("multispin_rounds_total") >= 1
    assert value("multispin_tokens_committed_total") >= 4 * 8
    assert 0.0 < value("multispin_acceptance_rate") < 1.0
    assert value("multispin_queue_depth") == 0
    assert value("multispin_draft_width") >= 1
    assert value("multispin_goodput_committed_tokens_per_s") > 0
    assert value("multispin_goodput_capped_tokens_per_s") > 0
    assert value("multispin_pool_free_pages") == 0      # synthetic: no pool
    assert re.search(r'^multispin_round_phase_seconds\{phase="draft"\} ',
                     text, re.M)
    assert re.search(r'^multispin_device_goodput_tokens_per_s\{rid="\d+"\} ',
                     text, re.M)

    # histogram families: cumulative le buckets, +Inf == _count, sum sane
    for fam in ("multispin_ttft_seconds", "multispin_round_seconds"):
        buckets = [
            (le, int(c)) for le, c in
            re.findall(rf'^{fam}_bucket{{le="([^"]+)"}} (\d+)$', text, re.M)]
        assert buckets and buckets[-1][0] == "+Inf"
        counts = [c for _, c in buckets]
        assert counts == sorted(counts), f"{fam} buckets not cumulative"
        assert counts[-1] == int(value(rf"{fam}_count"))
        assert value(rf"{fam}_sum") > 0
    assert value("multispin_ttft_seconds_count") == 4

    assert stats["rounds_total"] >= 1
    assert stats["scheduler"]["completed"] == 4
    assert stats["scheduler"]["goodput_capped"] > 0
    assert stats["ttft_sim_s"]["n"] == 4
    last = stats["last_round"]
    assert last["goodput_committed"] > 0
    assert last["t_draft"] >= 0 and last["t_round"] > 0


# ---------------------------------------------------------------------------
# error paths
# ---------------------------------------------------------------------------

class _NeverServable:
    """Stub backend: draws like SyntheticBackend but refuses everything."""

    def servable(self, request):
        return False

    def verify(self, lengths, requests, rng, key=None, mask=None,
               draft_width=1):  # pragma: no cover - nothing gets admitted
        raise AssertionError("unreachable")


def test_http_error_paths():
    async def run():
        gw, cli = await _start(_cell(max_batch=2))
        out = {}
        # unknown route -> 404
        with pytest.raises(GatewayError) as e404:
            await cli._call("GET", "/nope")
        out["404"] = e404.value
        # malformed generate -> 400
        with pytest.raises(GatewayError) as e400:
            await cli.generate(max_new_tokens=-3)
        out["400"] = e400.value
        with pytest.raises(GatewayError) as e400b:
            await cli.generate(alpha=7.5)
        out["400b"] = e400b.value
        # unknown stream -> 404
        with pytest.raises(GatewayError) as edel:
            await cli.delete_stream(12345)
        out["del"] = edel.value
        await gw.stop()
        return out

    out = asyncio.run(run())
    assert out["404"].status == 404
    assert out["400"].status == 400 and "max_new_tokens" in str(out["400"])
    assert out["400b"].status == 400 and "alpha" in str(out["400b"])
    assert out["del"].status == 404


def test_unservable_request_rejected_with_422():
    async def run():
        cell = MultiSpinCell(CellConfig(scheme="hete", max_batch=2, seed=0),
                             backend=_NeverServable())
        gw, cli = await _start(cell)
        with pytest.raises(GatewayError) as exc:
            await cli.generate(prompt_len=8, max_new_tokens=4)
        await gw.stop()
        return exc.value

    err = asyncio.run(run())
    assert err.status == 422
    assert err.body["error"] == "unservable"


def test_failed_round_ends_streams_with_its_cause():
    """A round that raises stops the step loop; the open stream gets an
    ``error`` event carrying the exception and later requests a 500, so
    clients see the cause at once instead of timing out."""

    def broken_step():
        raise RuntimeError("verify pass blew up")

    async def run():
        cell = _cell(max_batch=2)
        cell.step = broken_step
        gw, cli = await _start(cell)
        res = await asyncio.wait_for(
            cli.generate(prompt_len=8, max_new_tokens=4), timeout=10)
        with pytest.raises(GatewayError) as exc:
            await cli.generate(prompt_len=8, max_new_tokens=4)
        await gw.stop()
        return gw, res, exc.value

    gw, res, err = asyncio.run(run())
    assert isinstance(gw.round_error, RuntimeError)
    assert res.error == "round_failed" and not res.tokens
    assert err.status == 500
    assert err.body == {"error": "round_failed",
                        "detail": "RuntimeError: verify pass blew up"}


# ---------------------------------------------------------------------------
# explicit stream retirement (DELETE) on the synthetic backend
# ---------------------------------------------------------------------------

def test_delete_stream_retires_mid_session():
    async def run():
        gw, cli = await _start(_cell(max_batch=2))
        gen = cli.stream_generate(prompt_len=8, max_new_tokens=10 ** 6,
                                  alpha=0.8, T_S=0.009)
        ev = await gen.__anext__()
        rid = ev.data["rid"]
        # wait for at least one streamed round, then retire
        got_round = False
        retired = None
        async for ev in gen:
            if ev.event == "round" and not got_round:
                got_round = True
                resp = await cli.delete_stream(rid)
                assert resp["status"] == "retired"
            elif ev.event == "retired":
                retired = ev.data
                break
        await gen.aclose()
        active = [r.rid for r in gw.cell.scheduler.active]
        await gw.stop()
        return got_round, retired, active, rid

    got_round, retired, active, rid = asyncio.run(run())
    assert got_round and retired["rid"] == rid
    assert rid not in active


# ---------------------------------------------------------------------------
# MetricsHub unit behaviour (batch cell, no server)
# ---------------------------------------------------------------------------

def test_metrics_hub_on_batch_cell(tmp_path):
    trace = tmp_path / "trace.jsonl"
    cell = _cell(max_batch=4)
    hub = MetricsHub(window=3, trace_path=str(trace))
    hub.attach(cell)
    for i in range(4):
        cell.submit(Request(rid=i, prompt_len=8, max_new_tokens=12,
                            alpha=0.8, T_S=0.009))
    cell.run()
    hub.close()

    n_rounds = len(cell.history)
    assert hub.rounds_total == n_rounds
    assert len(hub.ring) == min(3, n_rounds)          # bounded ring
    committed = sum(int(r.accepted.sum()) for r in cell.history)
    assert hub.tokens_committed_total == committed
    assert hub.admitted_total == 4
    # acceptance identity vs raw history
    drafted = sum(int(r.lengths[r.active].sum()) for r in cell.history)
    positions = sum(int(np.maximum(r.accepted - 1, 0)[r.active].sum())
                    for r in cell.history)
    snap = hub.snapshot()
    assert snap["acceptance_total"] == pytest.approx(positions / drafted)
    # both goodput views surface and differ in the documented direction
    last = hub.latest
    s = cell.summary()
    assert last.goodput_committed == pytest.approx(s["goodput_committed"])
    assert last.goodput_capped == pytest.approx(s["goodput_capped"])
    assert s["goodput_committed"] >= s["goodput_capped"] > 0
    # JSONL trace: one parseable record per round
    rows = [json.loads(ln) for ln in trace.read_text().splitlines()]
    assert len(rows) == n_rounds
    assert rows[-1]["round_idx"] == n_rounds - 1
    assert rows[0]["accepted_tokens"] == int(cell.history[0].accepted.sum())


# ---------------------------------------------------------------------------
# loadgen percentile math
# ---------------------------------------------------------------------------

def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 10, 101):
        xs = rng.uniform(0, 100, n).tolist()
        for q in (0, 25, 50, 90, 95, 99, 100):
            assert percentile(xs, q) == pytest.approx(
                float(np.percentile(xs, q)), rel=1e-12)
    with pytest.raises(ValueError):
        percentile([], 50)
    s = summarize([3.0, 1.0, 2.0])
    assert s["n"] == 3 and s["p50"] == 2.0 and s["max"] == 3.0
    assert summarize([]) == {"p50": 0.0, "p90": 0.0, "p95": 0.0, "p99": 0.0,
                             "mean": 0.0, "max": 0.0, "n": 0}


# ---------------------------------------------------------------------------
# paged engine: disconnect retires the stream and returns its pages
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_disconnect_returns_pages_on_paged_engine():
    jax = pytest.importorskip("jax")
    from repro.api import EngineBackend, SpecEngine
    from repro.configs import get_config

    tcfg = get_config("qwen2.5-3b").smoke()
    dcfg = tcfg.replace(num_layers=1, d_model=32, num_heads=2, num_kv_heads=1,
                        head_dim=16, d_ff=64, name="draft-smoke")
    eng = SpecEngine(tcfg, dcfg, max_len=128, cache_kind="paged")
    eng.init_params(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 tcfg.vocab_size)
    backend = EngineBackend(eng, eng.start(prompts),
                            keep_finished_tokens=True)
    cell = MultiSpinCell(CellConfig(scheme="fixed", L_fixed=3, max_batch=2,
                                    seed=0), backend=backend)

    async def run():
        gw, cli = await _start(cell, step_barrier=2)
        # stream A runs to completion; stream B disconnects after one round
        a = asyncio.create_task(cli.generate(
            prompt_len=8, max_new_tokens=8, alpha=0.9, T_S=0.009))
        b = asyncio.create_task(cli.generate(
            prompt_len=8, max_new_tokens=10 ** 6, alpha=0.9, T_S=0.009,
            disconnect_after_rounds=1))
        res_a, res_b = await asyncio.gather(a, b)
        # the gateway notices the dropped socket and retires B
        for _ in range(200):
            if not any(r.rid == res_b.rid
                       for r in gw.cell.scheduler.active):
                break
            await asyncio.sleep(0.02)
        active = [r.rid for r in gw.cell.scheduler.active]
        await gw.stop()
        return res_a, res_b, active

    res_a, res_b, active = asyncio.run(run())
    assert res_a.done and len(res_a.tokens) == 8
    # real committed token ids, not positional surrogates
    assert all(isinstance(t, int) for t in res_a.tokens)
    vocab = get_config("qwen2.5-3b").smoke().vocab_size
    assert all(0 <= t < vocab for t in res_a.tokens)
    assert res_b.n_rounds == 1 and not res_b.done
    assert res_b.rid not in active
    # B's row was retired: its pages are back and the allocator is clean
    assert res_b.rid not in backend._row_of
    eng.t_pages.check_invariants()
    eng.d_pages.check_invariants()
    # only retired/finished rows may still hold pages; B's stream id is gone
    row_b = None  # retired — stream id no longer in the page manager
    assert row_b is None
    # the finished stream A's tokens match the engine's committed suffix
    # accounting (capped at max_new_tokens by the scheduler)
    assert len(res_a.tokens) == 8
