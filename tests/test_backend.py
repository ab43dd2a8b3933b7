"""Backend decisions and process layout, checked without touching a TPU.

  * the interpret-vs-lower choice is made from the backend in one place;
  * the compile cache follows ``JAX_COMPILATION_CACHE_DIR`` when it is set
    and otherwise the fixed in-checkout path that ``.gitignore`` lists;
  * ``repro.kernels`` imports on its own (no core -> runtime cycle), and
    ``repro.core`` pulls in no JAX;
  * replica workers and the plan scheduler's spawn pool are held to JAX's
    CPU backend, so a serving parent that holds the chip never has a child
    reaching for it.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.core.plan_scheduler import PlanScheduler
from repro.kernels import backend
from repro.launch.replica_worker import worker_env

REPO = Path(__file__).resolve().parents[1]


class TestPallasInterpret:
    @pytest.mark.parametrize("platform,interpret", [("cpu", True), ("tpu", False)])
    def test_backend_decides(self, monkeypatch, platform, interpret):
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        assert backend.pallas_interpret() is interpret

    def test_other_backend_raises(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="gpu"):
            backend.pallas_interpret()


class TestCompileCache:
    def test_env_var_is_left_to_jax(self, monkeypatch, tmp_path):
        monkeypatch.setenv(backend.CACHE_ENV, str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert backend.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_fixed_in_checkout_path_otherwise(self, monkeypatch):
        monkeypatch.delenv(backend.CACHE_ENV, raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            path = backend.use_compile_cache()
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        assert Path(path) == backend.default_cache_dir() == REPO / ".jax_cache"
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_kernels_import_alone_and_core_is_jax_free():
    code = ("import sys, repro.core; assert 'jax' not in sys.modules, 'core imported jax'; "
            "import repro.kernels")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr


class TestChildrenStayOnCpu:
    def test_replica_worker_env(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        env = worker_env()
        assert env["JAX_PLATFORMS"] == "cpu"
        assert str(REPO / "src") in env["PYTHONPATH"].split(os.pathsep)
        assert os.environ["JAX_PLATFORMS"] == "tpu"  # the parent's own is untouched

    def test_spawn_pool_initializer(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        s = PlanScheduler(workers=1, executor="process")
        s.start()
        try:
            ticket = s.submit("env", os.getenv, ("JAX_PLATFORMS",))[0]
            assert ticket.result(timeout=120) == "cpu"
        finally:
            s.close()
        assert os.environ["JAX_PLATFORMS"] == "tpu"
