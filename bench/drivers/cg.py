"""Closed-loop conjugate gradients through ``GraphServer.serve``.

One client runs HPCG-style CG: sets of ``iterations_per_set`` iterations,
each set from a fresh right-hand side ``b = A x*`` with ``x*`` drawn from the
seed (a pool of ``rhs_pool`` of them, used in turn).  Every iteration serves
one ``GraphRequest`` whose ``x`` is the current search direction; the vector
updates run in ``jnp`` on the device.  The window runs whole iterations until
``seconds`` have passed.

Set-up builds the matrix, plans it (the host partition), compiles the served
program and the CG update, and runs one iteration of each kind.  A reservoir
of ``check_sample`` iterations, drawn from the seed, keeps each one's ``x``
and served ``y`` for the comparison after the window.

A driver for another operator subclasses :class:`Driver` and overrides its
three hooks: ``matrix()`` (the configuration's operator), ``right_hand_side(rng)``
(one right-hand side from the seed's generator) and ``xla_rescale()`` (the
factor that keeps the XLA baseline's repeated products in range).

Traffic keys: ``iterations_per_set``, ``rhs_pool``, ``check_sample``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.gen import stencil
from bench.harness import Answer


def bench_cg_start(b):
    """``(x, r, p, r·r)`` of a CG set that starts from ``x = 0``."""
    return jnp.zeros_like(b), b, b, jnp.vdot(b, b)


def bench_cg_step(x, r, p, rr, ap):
    """One CG update, given ``ap = A p``; a converged set stays put
    instead of dividing by zero."""
    pap = jnp.vdot(p, ap)
    alpha = jnp.where(pap > 0, rr / jnp.where(pap > 0, pap, 1.0), 0.0)
    x = x + alpha * p
    r = r - alpha * ap
    rr_new = jnp.vdot(r, r)
    beta = jnp.where(rr > 0, rr_new / jnp.where(rr > 0, rr, 1.0), 0.0)
    return x, r, r + beta * p, rr_new


class Driver:
    def __init__(self, cell, spans) -> None:
        self.cell = cell
        self.spans = spans
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.start = jax.jit(bench_cg_start)
        self.step = jax.jit(bench_cg_step)

    def matrix(self) -> tuple[int, np.ndarray, np.ndarray, np.ndarray]:
        """``(n, rows, cols, vals)`` of the configuration's operator: HPCG's
        27-point stencil on the ``nx × ny × nz`` grid."""
        c = self.cfg
        n, rows, cols = stencil.coo(c["nx"], c["ny"], c["nz"])
        return n, rows, cols, stencil.hpcg_values(rows, cols)

    def right_hand_side(self, rng: np.random.Generator) -> np.ndarray:
        """``b = A x*`` with ``x*`` standard normal from ``rng``."""
        x_star = rng.standard_normal(self.n).astype(np.float32)
        return reference.spmv(self.n, self.rows, self.cols, self.vals, x_star)

    def xla_rescale(self) -> float:
        """The stencil's diagonal is 26: this keeps the XLA baseline's
        repeated products in range."""
        return 1.0 / stencil.DIAGONAL

    def setup(self) -> None:
        from repro.core import PartitionService
        from repro.runtime import GraphRequest, GraphServer

        self.GraphRequest = GraphRequest
        c = self.cfg
        self.n, self.rows, self.cols, self.vals = self.matrix()
        self.svc = PartitionService(max_entries=c["plan_cache_entries"])
        self.server = GraphServer(self.svc, k=c["k"], max_batch=c["max_batch"],
                                  compile_cache_entries=c["compile_cache_entries"],
                                  start_batcher=False)
        self.spans.wrap(self.server, "_plan_for", "plan_for")
        # Plan build and compile: the first request; then one CG iteration
        # of each program the window runs.
        b = jnp.ones(self.n, jnp.float32)
        first = self.server.serve(self._request(np.ones(self.n, np.float32)))
        first.y.block_until_ready()
        self.plan_build_s = first.info.partition_time_s
        self.fingerprint = first.info.fingerprint
        self.lane = first.info.bucket or "dedicated"
        state = self.start(b)
        state = self._iterate(state)
        jax.block_until_ready(state)

    def _request(self, x: np.ndarray):
        return self.GraphRequest(self.n, self.n, self.rows, self.cols, self.vals, x)

    def _iterate(self, state, record=None):
        x, r, p, rr = state
        with self.spans.span("d2h"):
            p_host = np.asarray(p)
        with self.spans.span("serve"):
            y = self.server.serve(self._request(p_host)).y
        if record is not None:
            record(p_host, y)
        with self.spans.span("cg_update"):
            return self.step(x, r, p, rr, y)

    def prepare(self, seed: int, seconds: float) -> None:
        rng = np.random.default_rng(seed)
        self.rhs = [jax.device_put(self.right_hand_side(rng).astype(np.float32))
                    for _ in range(int(self.traffic["rhs_pool"]))]
        jax.block_until_ready(self.rhs)
        self.sample_rng = np.random.default_rng([seed, 1])
        self.sample_size = int(self.traffic["check_sample"])
        self.sample: list[tuple[int, np.ndarray, object]] = []

    def _record(self, i: int):
        """Reservoir sampling: iteration ``i`` replaces a kept one with
        probability ``size / (i + 1)``."""
        def record(p_host, y):
            if len(self.sample) < self.sample_size:
                self.sample.append((i, p_host, y))
                return
            j = int(self.sample_rng.integers(0, i + 1))
            if j < self.sample_size:
                self.sample[j] = (i, p_host, y)
        return record

    def measure(self, seconds: float, run) -> None:
        per_set = int(self.traffic["iterations_per_set"])
        hist0 = dict(self.server.stats()["batch_hist"])
        i = 0
        state = None
        deadline = run.t0 + seconds
        while True:
            if i % per_set == 0:
                state = self.start(self.rhs[(i // per_set) % len(self.rhs)])
            state = self._iterate(state, self._record(i))
            i += 1
            if time.perf_counter() >= deadline:
                break
        jax.block_until_ready(state)
        run.iterations = run.attempted = i
        self.residual = float(state[3])
        hist1 = self.server.stats()["batch_hist"]
        run.counters["launches"] = sum(hist1.values()) - sum(hist0.values())

    def collect(self, run) -> list[Answer]:
        answers = [Answer(self.n, self.rows, self.cols, self.vals, p, np.asarray(y))
                   for _, p, y in self.sample]
        self.sample = []
        run.counters.update(
            answers_due=min(self.sample_size, run.iterations),
            plan_build_s=self.plan_build_s,
            lane=self.lane,
            spmv_per_window=run.iterations,
            last_rr=self.residual,
            n_rows=self.n,
            n_cols=self.n,
            nnz=int(self.rows.size),
        )
        return answers

    def close(self) -> None:
        self.server.close()
        self.svc.close()

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
        n, scale = self.n, self.xla_rescale()

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
