"""Compile the served EP-SpMV kernels for one chip of a described TPU v5e.

Nothing runs on a chip: each test lowers one jitted serve function for one
device of a ``v5e:2x2`` topology and checks that Mosaic produced a
``tpu_custom_call`` — the refusals (tile alignment, 1-D gathers, VMEM) that
interpret mode cannot show.  The topology is described inside a fixture,
never at import, so every pytest-xdist worker collects the same tests and
only the worker given this file loads the TPU compiler.  The backend here
is the CPU, which interprets; the fixture steers the kernels to lower.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import BucketSpec, backend, make_bucketed_spmv_fn
from repro.runtime import BucketKey


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        mp.setattr(backend, "pallas_interpret", lambda: False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _compiled_text(spec: BucketSpec, sharding) -> str:
    b, k = spec.batch, spec.k
    shapes = [
        ((b, k, spec.e_max), jnp.float32),
        ((b, k, spec.e_max), jnp.int32),
        ((b, k, spec.e_max), jnp.int32),
        ((b, k, spec.x_max), jnp.int32),
        ((b, k, spec.y_max), jnp.int32),
        ((b, spec.n_cols), jnp.float32),
    ]
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return make_bucketed_spmv_fn(spec).lower(*args).compile().as_text()


@pytest.mark.parametrize("mode", ["software", "streaming"])
def test_svc_batched_bucket(one_chip, mode):
    # The (512, 512, 2048) bucket of benchmarks/svc_batched.py: k=8, batch 8.
    spec = BucketKey(n_rows=512, n_cols=512, nnz=2048, k=8, mode=mode).spec(batch=8)
    assert "tpu_custom_call" in _compiled_text(spec, one_chip)


@pytest.mark.parametrize("tile", [1024, 1280])
def test_stencil_widths_with_combine(one_chip, tile):
    # The 64^3 27-point stencil at k=1024 through the dedicated lane; its
    # churned plans widen the x/y tiles past one 1024-lane slab.
    spec = BucketSpec(k=1024, n_rows=262144, n_cols=262144, e_max=6912,
                      x_max=tile, y_max=tile, batch=1)
    text = _compiled_text(spec, one_chip)
    assert "tpu_custom_call" in text and "scatter" in text


def test_pad_below_lane_width(one_chip):
    # pad=8 tiles and k=4 (not a multiple of 8) are widened to legal tiles.
    spec = BucketKey(n_rows=256, n_cols=256, nnz=1024, k=4, mode="software").spec(
        batch=2, pad=8)
    assert spec.e_max % 128 and spec.k % 8
    assert "tpu_custom_call" in _compiled_text(spec, one_chip)
