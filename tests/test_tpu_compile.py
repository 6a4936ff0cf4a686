"""Compile-only checks of the device programs for a described TPU v5e.

Nothing runs: each test lowers and compiles one device program for a v5e
chip that is described, not attached — the serving sweep's replay at its
real shape (5 technologies x 2^20 events) and the jitted DSE grid at the
paper grid's shapes; the replay programs take their float64 keys as int64
IEEE bits and split them into int32 words inside.  What the chip's
compiler would refuse — an unaligned block, a 64-bit Mosaic operand, a
lowering that takes minutes — fails here at no chip time.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test workers import every file.  All
such tests stay in this one file, so one worker loads it.
"""

import dataclasses
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = pytest.importorskip("jax.numpy")

from repro.core.access_counts import MemoryParams  # noqa: E402
from repro.core.bandwidth import ArrayConfig  # noqa: E402
from repro.core.memory_system import DRAMModel  # noqa: E402
from repro.core.workload import nlp_model_zoo  # noqa: E402
from repro.device import x64  # noqa: E402
from repro.dse import GeomAxes, GridSpec  # noqa: E402
from repro.dse.access import entity_size_grid  # noqa: E402
from repro.dse.geomgrid import _design_points, _geom_ppa_fields  # noqa: E402
from repro.dse.grid import PPAGrid, _compute_time_grid, _jitted_eval  # noqa: E402
from repro.kernels.segmented_replay import ops  # noqa: E402

R, N = 5, 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A described-chip compile cannot be read back from the cache without a
    chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip, dtype):
    return jax.ShapeDtypeStruct((R, N), dtype, sharding=one_chip)


def test_replay_running_max_compiles(one_chip):
    with x64():
        x = _spec(one_chip, jnp.int64)
        ops._replay_cummax.lower(x, scan="lax", chunk=ops.DEFAULT_CHUNK,
                                 interpret=False).compile()


def test_replay_depth_searchsorted_compiles(one_chip):
    with x64():
        x = _spec(one_chip, jnp.int64)
        ops._replay_search.lower(x, x).compile()


def test_pallas_cummax_compiles_to_mosaic(one_chip):
    with x64():
        x = _spec(one_chip, jnp.int64)
        compiled = ops._replay_cummax.lower(
            x, scan="pallas", chunk=ops.DEFAULT_CHUNK, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("grid", ["workload", "geometry"])
def test_dse_grid_compiles(one_chip, grid):
    """The paper grid on its longest layer axis (gpt3, 866 layers)."""
    spec, wl = GridSpec(), nlp_model_zoo()["gpt3"]
    if grid == "workload":
        ppa = PPAGrid.build(spec.technologies, spec.capacities_mb)
        ppa_fields = tuple(getattr(ppa, f.name) for f in dataclasses.fields(ppa))
    else:
        designs, _ = _design_points(spec.technologies, GeomAxes())
        ppa_fields = _geom_ppa_fields(designs, spec.capacities_mb)
    args = (
        entity_size_grid(wl, spec.batches, spec.d_w),
        np.asarray(spec.capacities_mb, np.float64),
        ppa_fields,
        _compute_time_grid(wl, spec, ArrayConfig()),
    )
    fn = _jitted_eval(tuple(spec.modes), MemoryParams(), DRAMModel())
    with x64():
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            args,
        )
        fn.lower(*shapes).compile()
