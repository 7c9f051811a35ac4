"""The jitted planner and simulator programs compile for a v5e chip.

Nothing runs here.  Each program is lowered from `ShapeDtypeStruct`s
placed on device 0 of a described (not attached) ``v5e:2x2`` topology
and compiled by the TPU compiler, at the shapes of the m=10,000 tier:
VecCluster capacities (8192, 8) and latency-table chunks of 16384 rows.
What the chip's compiler refuses (an unsupported float64 op, a program
that does not fit in HBM) fails here, at no chip time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The persistent compile cache is off around these compiles — an
entry written for a described chip cannot be read back without one.
"""
import pytest

CAP_D, CAP_N = 8192, 8        # VecCluster capacities at m=10,000
ROWS = 16384                  # padded rows of one bulk latency-table chunk
HBM_BYTES = 16 * 2 ** 30      # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


def _sds(sharding, shape, dtype="float64"):
    import jax
    import numpy as np
    return jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=sharding)


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used} bytes > one chip's HBM"
    return compiled


def _state_width():
    from repro.core import perf_model_jax as pmj
    return len(pmj.SLOT_FIELDS) * CAP_N + len(pmj.ROW_FIELDS)


def test_alloc_all_compiles_for_v5e(one_chip):
    from repro.core import perf_model_jax as pmj
    from repro.core import perf_model_vec as pmv
    from repro.core.types import V5E
    _compile(pmj._alloc_all_jit, V5E,
             _sds(one_chip, (CAP_D, _state_width())),
             _sds(one_chip, (len(pmv.COEFF_FIELDS) + 4,)))


def test_dirty_row_scatter_compiles_for_v5e(one_chip):
    from repro.core import perf_model_jax as pmj
    _compile(pmj._scatter_rows_jit, _sds(one_chip, (CAP_D, _state_width())),
             _sds(one_chip, (pmj.K_ROWS, _state_width() + 1)))


@pytest.mark.parametrize("n_co", [1, 2, 4])
def test_latency_tables_compile_for_v5e(one_chip, n_co):
    from repro.core.types import V5E
    from repro.serving import physics_jax
    _compile(physics_jax._tables_jit, V5E, n_co,
             *(_sds(one_chip, (ROWS, n_co)) for _ in range(8)))
