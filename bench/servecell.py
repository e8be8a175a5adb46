"""Build the served cell of a benchmark configuration.

The benchmark's own copy of the steps of ``repro.launch.gateway.build_cell``
for the engine backend: model configs from the configuration file, a paged
``SpecEngine`` on the compiled round path, seeded weights (with the
acceptance construction), both made by each model's architecture module
(``arch.of``), an ``EngineBackend`` and a ``MultiSpinCell``.  Two departures, both so that
set-up does only what the cell's traffic needs:

* every request is admitted through ``add_streams`` with its own prompt:
  the streams ``engine.start`` prefills for the batch shape are bound to
  placeholder requests and released at once, instead of being handed to
  the first requests with a made-up prompt;
* warm-up covers exactly the round buckets the scheme can dispatch
  (``(max_batch, L)`` for L in 1..L_max, at the configuration's draft width),
  the prompt buckets and prompt lengths the run's traffic draws, and the
  page-table updates of 1..max_batch rows that rounds and admissions make.
  The harness logs any program still compiled or loaded in the window.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import arch
import weights


class ServedCell:
    """Engine, backend and cell of one configuration, built from a seed."""

    def __init__(self, config: dict, seed: int):
        from repro.api import CellConfig, EngineBackend, MultiSpinCell
        from repro.core.channel import ChannelConfig
        from repro.serving.spec_engine import SpecEngine

        self.config = config
        srv = config["serving"]
        dtype = srv["dtype"]
        t_arch, d_arch = arch.of(config["target"]), arch.of(config["draft"])
        self.tcfg = t_arch.model_config(config["target"],
                                        config["name"] + ".target", dtype)
        self.dcfg = d_arch.model_config(config["draft"],
                                        config["name"] + ".draft", dtype)
        self.max_batch = int(srv["max_batch"])
        self.max_len = int(srv["max_len"])
        self.L_max = int(srv["L_max"])
        self.J = int(srv.get("draft_width", 1))
        self.vhat = int(srv["vhat"])
        engine = SpecEngine(
            self.tcfg, self.dcfg, max_len=self.max_len, cache_kind="paged",
            page_size=int(srv["page_size"]),
            num_pages=self.max_batch * 2 * (self.max_len
                                            // int(srv["page_size"])),
            compile_mode=srv["compile"], cache_dtype=weights.DTYPES[dtype])
        kt, kd = weights.pair_keys(seed)
        acc = config["acceptance"]
        engine.t_params = t_arch.make_params(config["target"], acc, kt, dtype)
        engine.d_params = d_arch.make_params(config["draft"], acc, kd, dtype)
        for got, model in ((engine.t_params, engine.target),
                           (engine.d_params, engine.draft)):
            want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            if (jax.tree.structure(got) != jax.tree.structure(want)
                    or jax.tree.map(lambda a, b: a.shape == b.shape
                                    and a.dtype == b.dtype, got, want)
                    != jax.tree.map(lambda a: True, want)):
                raise ValueError("seeded weights do not match the model's "
                                 "parameter layout")
        jax.block_until_ready((engine.t_params, engine.d_params))
        self.engine = engine
        # the batch shape: max_batch streams prefilled by start(), bound to
        # placeholder requests and released at once, so that every real
        # request is admitted through add_streams with its own prompt
        from repro.serving.scheduler import Request

        state = engine.start(jnp.zeros((self.max_batch, 8), jnp.int32))
        self.backend = EngineBackend(engine, state, vhat=self.vhat,
                                     keep_finished_tokens=True)
        holders = [Request(rid=-1 - i, prompt_len=8, max_new_tokens=1)
                   for i in range(self.max_batch)]
        self.backend.bind(holders)
        self.backend.release(holders)
        for r in holders:
            self.backend.drop_finished(r.rid)
        self.cell = MultiSpinCell(
            CellConfig(scheme=srv["scheme"],
                       scheme_params=dict(srv.get("scheme_params", {})),
                       schedule=srv["schedule"], max_batch=self.max_batch,
                       channel=ChannelConfig(vocab_size=self.tcfg.vocab_size),
                       t_ver_fix=float(srv["t_ver_fix"]),
                       t_ver_lin=float(srv["t_ver_lin"]), L_max=self.L_max,
                       seed=seed % (2 ** 31)),
            backend=self.backend,
            rng=np.random.default_rng(seed))

    # -- warm-up ---------------------------------------------------------

    def warm(self, prompt_lengths) -> None:
        """Compile what the run's traffic will dispatch: the small eager
        ops an admission runs per prompt length (the backend turns the
        prompt list into an int32 row; ``add_streams`` pads it to its bucket
        with shape-specific ops), the prefill of every prompt bucket, the
        page-table row updates of every size, and every round bucket the
        scheme can plan, (max_batch, L) for L in 1..L_max at the configured
        draft width, through real rounds on placeholder streams."""
        eng, be = self.engine, self.backend
        lengths = sorted({int(n) for n in prompt_lengths})
        for M in lengths:
            p = jnp.asarray([0] * M, jnp.int32)[None, :]
            Mb = eng.prompt_bucket(M)
            if Mb > M:
                jnp.concatenate([p, jnp.tile(p[:, -1:], (1, Mb - M))], axis=1)
        # the device page table takes the rows changed since its last use
        # in one scatter, of 1..max_batch rows
        for mgr in (eng.t_pages, eng.d_pages):
            mgr.device_table(range(self.max_batch))
            table = mgr._dev
            for k in range(1, self.max_batch + 1):
                table.at[jax.device_put(np.arange(k, dtype=np.int32))].set(
                    jax.device_put(np.zeros((k, table.shape[1]), np.int32)))
        for Mb in sorted({eng.prompt_bucket(M) for M in lengths}):
            be.state, rows = eng.add_streams(be.state,
                                             jnp.zeros((1, Mb), jnp.int32))
            for row in rows:
                eng.retire_stream(row)
        prompt = jnp.zeros((1, eng.prompt_bucket(lengths[0])), jnp.int32)
        rows = []
        for _ in range(self.max_batch):
            be.state, r = eng.add_streams(be.state, prompt)
            rows += r
        key = jax.random.PRNGKey(0)
        for L in range(1, self.L_max + 1):
            be.state, _, _ = eng.spin_round(
                be.state, np.full(self.max_batch, L, np.int64), key,
                vhat=self.vhat, draft_width=self.J)
        jax.block_until_ready(be.state.pending)
        for row in rows:
            eng.retire_stream(row)

    def free(self) -> None:
        """Drop every device array of the program (weights, KV pools,
        stream state), so that the check that follows has the chip."""
        eng = self.engine
        eng.t_params = eng.d_params = eng.t_cache = eng.d_cache = None
        self.backend.state = None
        self.cell.backend = None
