"""Personalized PageRank by conjugate gradients through ``GraphServer.serve``.

The closed loop of :mod:`bench.drivers.cg` on the configuration's Graph500
Kronecker graph (:mod:`bench.gen.kronecker`): the matrix is PageRank's
symmetric form ``I − α D^{-1/2} W D^{-1/2}``, and each set of
``iterations_per_set`` iterations answers one personalized query from
``b = (1 − α) D^{-1/2} s``, with ``s`` uniform over ``sources_per_query``
non-isolated vertices drawn from the seed (a pool of ``rhs_pool`` queries,
used in turn).  A set runs all its iterations with no tolerance stop
(GAP's ``pr`` caps PageRank at 20), so every seed does the same work.  The
graph comes from the configuration's ``graph_seed``: every seed serves the
same matrix.

Traffic keys: ``iterations_per_set``, ``sources_per_query``, ``rhs_pool``,
``check_sample``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers import cg
from bench.gen import kronecker


class Driver(cg.Driver):
    def setup(self) -> None:
        from repro.core import PartitionService
        from repro.runtime import GraphRequest, GraphServer

        self.GraphRequest = GraphRequest
        c = self.cfg
        self.n, self.rows, self.cols, self.vals, self.degree = kronecker.graph500_ppr(c)
        self.svc = PartitionService(max_entries=c["plan_cache_entries"])
        self.server = GraphServer(self.svc, k=c["k"], max_batch=c["max_batch"],
                                  compile_cache_entries=c["compile_cache_entries"],
                                  start_batcher=False)
        self.spans.wrap(self.server, "_plan_for", "plan_for")
        # Plan build and compile: the first request; then one CG iteration
        # of each program the window runs.
        first = self.server.serve(self._request(np.ones(self.n, np.float32)))
        first.y.block_until_ready()
        self.plan_build_s = first.info.partition_time_s
        self.fingerprint = first.info.fingerprint
        self.lane = first.info.bucket or "dedicated"
        state = self.start(jnp.ones(self.n, jnp.float32))
        state = self._iterate(state)
        jax.block_until_ready(state)

    def prepare(self, seed: int, seconds: float) -> None:
        rng = np.random.default_rng(seed)
        alpha = float(self.cfg["damping"])
        sources = int(self.traffic["sources_per_query"])
        self.rhs = []
        for _ in range(int(self.traffic["rhs_pool"])):
            s = kronecker.personalization(self.degree, sources, rng)
            b = kronecker.ppr_rhs(self.degree, s, alpha)
            self.rhs.append(jax.device_put(b.astype(np.float32)))
        jax.block_until_ready(self.rhs)
        self.sample_rng = np.random.default_rng([seed, 1])
        self.sample_size = int(self.traffic["check_sample"])
        self.sample: list[tuple[int, np.ndarray, object]] = []

    def after_trace(self, run, log) -> None:
        """Traced runs only, after the window: the pack plan's modelled
        loads and XLA's own scatter SpMV on the same matrix (device ms) —
        baselines for PERF.md, not metrics."""
        from repro.kernels.ops import spmv_hbm_traffic_model

        sp = self.svc.plan_cache.peek(self.fingerprint)
        if sp is not None:
            log(f"info spmv_hbm_traffic_model={spmv_hbm_traffic_model(sp.plan)}")
        rows = jnp.asarray(self.rows, jnp.int32)
        cols = jnp.asarray(self.cols, jnp.int32)
        vals = jnp.asarray(self.vals)
        n = self.n
        # The matrix's eigenvalues lie in [1 − α, 1 + α]: this keeps
        # repeated products in range.
        scale = 1.0 / (1.0 + float(self.cfg["damping"]))

        @jax.jit
        def bench_xla_spmv(rows, cols, vals, x):
            y = jnp.zeros(n, jnp.float32).at[rows].add(vals * x[cols])
            return y * scale

        x = jnp.ones(n, jnp.float32)
        bench_xla_spmv(rows, cols, vals, x).block_until_ready()
        reps, t0 = 0, time.perf_counter()
        while time.perf_counter() - t0 < 0.5:
            x = bench_xla_spmv(rows, cols, vals, x)
            reps += 1
        x.block_until_ready()
        log(f"info xla_scatter_spmv_ms={(time.perf_counter() - t0) * 1e3 / reps}")
