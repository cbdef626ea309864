"""Compile guard: the forest's Pallas kernels compile for a TPU v5e.

Each case lowers one kernel at the widths ``chip_smoke.py`` runs (F = 10
features, T·M = 16 x 1023 tables, folded, or one group per member in the
absorb; C = 64 bins, B = 4096 rows) for
one chip of a *described* ``v5e:2x2`` topology and asserts that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).  Nothing
runs and no chip is needed: this catches what interpret mode cannot — a
block shape the TPU tiling refuses, an op Mosaic cannot lower, a kernel
that outgrows its VMEM — at no chip time.  A compile that passes is not
a chip run.

The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops  # noqa: F401  (import order: ops first)
from repro.kernels.qo_merge import qo_merge_pallas
from repro.kernels.qo_query_batched import qo_query_batched_pallas
from repro.kernels.qo_route import ATTR_LANES, qo_route_pallas
from repro.kernels.qo_update_leaves import (FOREST_ROWS,
                                            qo_update_leaves_pallas,
                                            round_up)
from repro.kernels.sketch_compact import sketch_compact_pallas

T, M, F, C, B = 16, 1023, 10, 64, 4096
MP, CP = round_up(T * M, 128), round_up(C, 128)   # ops.pack_forest's layout
MP_G = round_up(M, 128)                 # one member's tables in the absorb
ROWS = round_up(T * M * F, 256)                   # merge/compact row tiles
PLIES = 12                                        # HTRConfig.max_depth


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off: an entry compiled for a described chip cannot be read back here
    and would only warn on the next lookup."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


f32, i32 = jnp.float32, jnp.int32
KERNELS = {
    "qo_update_leaves": (
        functools.partial(qo_update_leaves_pallas, n_bins=C),
        [((F, FOREST_ROWS, T * MP_G, CP), f32), ((T, 1, B), i32),
         ((F, 1, B), f32), ((1, B), f32), ((T, 1, B), f32)]),
    "qo_query_batched": (
        qo_query_batched_pallas, [((F, FOREST_ROWS, MP, CP), f32)]),
    "qo_route": (
        functools.partial(qo_route_pallas, plies=PLIES),
        [((B, 128), f32), ((T, round_up(M, 8), ATTR_LANES), f32)]),
    "qo_merge": (
        qo_merge_pallas, [((4, ROWS, CP), f32), ((4, ROWS, CP), f32)]),
    "sketch_compact": (     # a C-slot sketch merged with a C-slot batch
        functools.partial(sketch_compact_pallas, k_out=C),
        [((5, ROWS, round_up(2 * C, 128)), f32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = KERNELS[name]
    compiled = jax.jit(fn).lower(*_shapes(one_chip, *specs)).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name}: no Mosaic kernel in the compiled program"
