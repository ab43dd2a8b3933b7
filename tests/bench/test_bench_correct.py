"""``correct`` comes out false when the timed path is broken underneath.

Each test drives a whole run of a cell (set-up, window, comparison) at a
tiny size on the CPU, skipping only the run's look for a chip, with the
program's answers replaced where they are produced: by the control (the
reference in bfloat16), by an altered answer, or by a step that returns its
input unchanged.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference
from repro.runtime import request as program
from repro.runtime.request import ServeResult

BENCH = harness.load_benchmark()
SEED = 2**33 + 17  # wider than 32 bits, as the check's seeds are


def tiny(name: str) -> harness.Cell:
    return harness.tiny(BENCH, name)


def run(cell: harness.Cell) -> dict:
    return harness.run_cell(cell, SEED, 0.3, False, time.perf_counter(), log=lambda m: None)


def control(req, y):
    return reference.control_spmv(req.n_rows, req.rows, req.cols, req.vals, req.x)


def altered(req, y):
    return np.asarray(y).copy() + np.eye(1, req.n_rows, req.n_rows // 2, dtype=np.float32)[0]


def unchanged(req, y):
    return req.x[: req.n_rows]


def break_serve(monkeypatch, fault):
    """The synchronous lane answers with ``fault(request, y)``."""
    serve = program.GraphServer.serve

    def broken(self, request):
        res = serve(self, request)
        return ServeResult(y=jnp.asarray(fault(request, res.y)), info=res.info)

    monkeypatch.setattr(program.GraphServer, "serve", broken)


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_a_sound_run_is_correct(name):
    out = run(tiny(name))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in tiny(name).end_to_end}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [control, altered, unchanged], ids=lambda f: f.__name__)
def test_cg_cell_fails_when_its_answers_are_wrong(monkeypatch, fault):
    break_serve(monkeypatch, fault)
    out = run(tiny("hpcg-48.cg"))
    assert not out["correct"]
    assert out["checks"]["max_rel_err"]["value"] > out["checks"]["max_rel_err"]["limit"]


@pytest.mark.parametrize("fault", [altered, unchanged], ids=lambda f: f.__name__)
def test_ppr_cell_fails_when_its_answers_are_wrong(monkeypatch, fault):
    break_serve(monkeypatch, fault)
    out = run(tiny("graph500-16.ppr_cg"))
    assert not out["correct"]
    assert out["checks"]["max_rel_err"]["value"] > out["checks"]["max_rel_err"]["limit"]
