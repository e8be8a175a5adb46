"""Traffic generator: one general generator that reads a traffic file.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

    loop         "closed" (``clients`` callers, each sending its next
                 request when the last one ends, after an exponential think
                 time of mean ``think_s``) or "open" (arrivals at
                 ``rate_per_s``, Poisson, optionally on/off modulated by
                 ``burst`` = {"on_s", "off_s", "on_factor", "off_factor"});
    prompt_len   {"dist": "loguniform" | "uniform" | "fixed", "lo", "hi"};
    output_len   the same form, the request's ``max_new_tokens``;
    prefix       optional {"len": <length form>, "n_distinct": k}: every
                 prompt starts with one of k shared prefixes;
    tasks        task names, drawn uniformly; each request's ``alpha``
                 prior is its task's rate in ``task_alpha``; its prompt
                 tokens come from the task's range of the vocabulary;
    T_S          {"base", "jitter": [lo, hi]}: device drafting speed,
                 base times a uniform factor;
    table_size   how many requests the table holds;
    table_seed   the seed of the table of sizes, tasks and arrival gaps;
    shuffle_block  the run seed reorders requests within consecutive
                 blocks of this many;
    warm_requests  set-up warms the prompt lengths of this many requests
                 from the table's start (more than a run reaches).

Every run seed gets the same table of sizes, tasks and gaps, reordered
only within blocks, and its own token ids: the requests a run reaches are
the same for every seed, so two seeds offer the same work.

The generator drives the gateway over HTTP/SSE and times each request from
when it was DUE (its arrival, or its client's last request ending), so a
stall that delays later sends shows in their latency; every SSE event gets
its arrival time.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time

import numpy as np


@dataclasses.dataclass
class Req:
    index: int
    prompt: list
    max_new_tokens: int
    task: str
    alpha: float
    T_S: float
    gap_s: float = 0.0          # open loop: time since the previous arrival


@dataclasses.dataclass
class Outcome:
    req: Req
    due: float
    sent: float = 0.0
    rid: int | None = None
    events: list = dataclasses.field(default_factory=list)  # (t, n, tokens)
    done_t: float | None = None
    error: str | None = None

    @property
    def first_t(self):
        return self.events[0][0] if self.events else None

    @property
    def tokens(self) -> list:
        return [t for _, _, toks in self.events for t in toks]


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    dist = spec.get("dist", "fixed")
    if dist == "fixed":
        return np.full(n, int(spec["value"]))
    lo, hi = float(spec["lo"]), float(spec["hi"])
    if dist == "uniform":
        return rng.integers(int(lo), int(hi) + 1, n)
    if dist == "loguniform":
        return np.floor(np.exp(rng.uniform(np.log(lo), np.log(hi + 1), n))
                        ).astype(int).clip(int(lo), int(hi))
    raise ValueError(f"unknown length distribution {dist!r}")


def build_table(traffic: dict, seed: int, vocab: int, task_ranges) -> list:
    """The run's requests, in the order the run sends them.

    ``task_ranges[i]`` is the (lo, hi) token-id range of task i."""
    n = int(traffic["table_size"])
    trng = np.random.default_rng(int(traffic["table_seed"]))
    plen = _lengths(traffic["prompt_len"], n, trng)
    olen = _lengths(traffic["output_len"], n, trng)
    tasks = trng.integers(0, len(traffic["tasks"]), n)
    ts = traffic["T_S"]
    t_s = ts["base"] * trng.uniform(*ts["jitter"], n)
    gaps = np.zeros(n)
    if traffic["loop"] == "open":
        gaps = trng.exponential(1.0, n) / float(traffic["rate_per_s"])
    prefix = traffic.get("prefix")
    rng = np.random.default_rng([int(seed) % (2 ** 32), int(seed) >> 32,
                                 0x51ED])
    # the seed shuffles within blocks, so every run's first k blocks hold
    # the same requests
    block = int(traffic["shuffle_block"])
    order = np.concatenate([i + rng.permutation(min(block, n - i))
                            for i in range(0, n, block)])
    prefixes = []
    if prefix:
        k = int(prefix["n_distinct"])
        pl = _lengths(prefix["len"], k, trng)
        prefixes = [rng.integers(*task_ranges[i % len(task_ranges)], p)
                    for i, p in enumerate(pl)]
    table = []
    for j, i in enumerate(order):
        lo, hi = task_ranges[tasks[i]]
        body = rng.integers(lo, hi, int(plen[i]))
        if prefixes:
            pre = prefixes[int(rng.integers(len(prefixes)))]
            body = np.concatenate([pre, body[len(pre):]]) \
                if len(pre) < len(body) else pre[:len(body)]
        table.append(Req(index=j, prompt=[int(t) for t in body],
                         max_new_tokens=int(olen[i]),
                         task=traffic["tasks"][tasks[i]],
                         alpha=float(traffic["task_alpha"][tasks[i]]),
                         T_S=float(t_s[i]), gap_s=float(gaps[i])))
    return table


def _burst_factor(burst: dict | None, t: float) -> float:
    if not burst:
        return 1.0
    period = burst["on_s"] + burst["off_s"]
    return (burst["on_factor"] if (t % period) < burst["on_s"]
            else burst["off_factor"])


async def _parse_sse(reader):
    event, data = "message", []
    while True:
        line = await reader.readline()
        if not line:
            return
        text = line.decode("utf-8").rstrip("\r\n")
        if not text:
            if data:
                yield event, json.loads("\n".join(data))
            event, data = "message", []
            continue
        field, _, value = text.partition(":")
        value = value.removeprefix(" ")
        if field == "event":
            event = value
        elif field == "data":
            data.append(value)


async def _one(host: str, port: int, out: Outcome, timeout_s: float):
    body = json.dumps({"prompt": out.req.prompt,
                       "max_new_tokens": out.req.max_new_tokens,
                       "task": out.req.task, "alpha": out.req.alpha,
                       "T_S": out.req.T_S}).encode()
    head = (f"POST /v1/generate HTTP/1.1\r\nHost: {host}\r\n"
            f"Connection: close\r\nContent-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode("latin-1")
    out.sent = time.perf_counter()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(head + body)
        await writer.drain()
        status = (await reader.readline()).decode("latin-1").split()
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if len(status) < 2 or status[1] != "200":
            out.error = f"http {status[1] if len(status) > 1 else '?'}"
            return

        async def consume():
            async for event, data in _parse_sse(reader):
                now = time.perf_counter()
                if event == "queued":
                    out.rid = data.get("rid")
                elif event == "round":
                    out.events.append((now, int(data["n"]),
                                       list(data["tokens"])))
                elif event == "done":
                    out.done_t = now
                    return
                elif event in ("error", "retired"):
                    out.error = data.get("error", event)
                    return

        await asyncio.wait_for(consume(), timeout_s)
    except asyncio.TimeoutError:
        out.error = "timeout"
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionError, OSError):
            pass


class LoadGen:
    """Drives a gateway with a traffic table until ``stop()``."""

    def __init__(self, traffic: dict, table: list, host: str, port: int,
                 seed: int, timeout_s: float = 120.0):
        self.traffic = traffic
        self.table = table
        self.host, self.port = host, port
        self.timeout_s = timeout_s
        self.outcomes: list[Outcome] = []
        self._next = 0
        self._stop = asyncio.Event()
        self._tasks: set = set()
        self._rng = np.random.default_rng([int(seed) % (2 ** 32), 7])

    def _take(self):
        if self._next >= len(self.table):
            raise RuntimeError("the traffic table ran out; raise table_size")
        req = self.table[self._next]
        self._next += 1
        return req

    async def _client(self):
        think = float(self.traffic.get("think_s", 0.0))
        due = time.perf_counter()
        while not self._stop.is_set():
            out = Outcome(req=self._take(), due=due)
            self.outcomes.append(out)
            await _one(self.host, self.port, out, self.timeout_s)
            due = time.perf_counter()
            if think > 0:
                due += self._rng.exponential(think)
                await asyncio.sleep(max(0.0, due - time.perf_counter()))

    async def _arrivals(self):
        t0 = time.perf_counter()
        due = t0
        burst = self.traffic.get("burst")
        while not self._stop.is_set():
            req = self._take()
            due += req.gap_s / _burst_factor(burst, due - t0)
            delay = due - time.perf_counter()
            if delay > 0:
                try:
                    await asyncio.wait_for(self._stop.wait(), delay)
                    return
                except asyncio.TimeoutError:
                    pass
            out = Outcome(req=req, due=due)
            self.outcomes.append(out)
            task = asyncio.create_task(
                _one(self.host, self.port, out, self.timeout_s))
            self._tasks.add(task)
            task.add_done_callback(self._tasks.discard)

    def start(self):
        if self.traffic["loop"] == "closed":
            coros = [self._client() for _ in range(int(self.traffic["clients"]))]
        else:
            coros = [self._arrivals()]
        for c in coros:
            task = asyncio.create_task(c)
            self._tasks.add(task)
            task.add_done_callback(self._tasks.discard)

    def stop_sending(self):
        self._stop.set()

    async def cut(self):
        """Close every connection still open (the gateway retires those
        streams as client disconnects) and wait for the tasks to end."""
        self._stop.set()
        tasks = set(self._tasks)
        for task in tasks:
            task.cancel()
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)
