"""Compile the main path's Pallas kernels for a v5e chip that is
described, not attached (the TPU compiler ships with jaxlib).

Interpret mode hides what Mosaic refuses — block shapes off the (8, 128)
tiling, in-kernel gathers, dynamic slices of values, selects on bools —
so each kernel is lowered here with ``interpret=False`` at the size the
main path runs it: the cohort-step megakernel and the dirty-row slab
kernel at the fleet's 160 slots x 16 words, the scheduler's conflict
kernels at 256 transactions x 32 words.  Nothing runs; a refusal raises.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import conflict as KC
from repro.kernels import megastep as MS

N_SLOTS, SLOT_WORDS, LANES, SLAB = 160, 16, 4, 40
N_TXN, TXN_WORDS = 256, 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache
    # but cannot be read back without one: keep it out of any cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Lower ``fn`` for the described chip; return the compiled HLO."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


U32, I32, BOOL = jnp.uint32, jnp.int32, jnp.bool_


def test_megastep_compiles_vmapped_over_lanes(one_chip):
    n, w, lanes = N_SLOTS, SLOT_WORDS, LANES
    fn = jax.vmap(lambda *a: MS.megastep(*a, interpret=False))
    hlo = _compile(fn, one_chip, *([((lanes, n, w), U32)] * 3),
                   ((lanes, n), I32), *([((lanes, n), BOOL)] * 4))
    assert "tpu_custom_call" in hlo


def test_rowslab_compiles(one_chip):
    n, w, k = N_SLOTS, SLOT_WORDS, SLAB
    fn = lambda *a: MS.rowslab(*a, interpret=False)  # noqa: E731
    hlo = _compile(fn, one_chip, ((n, w), U32), ((n, w), U32),
                   ((n, n), BOOL), ((n, n), BOOL), ((n,), I32),
                   ((n,), BOOL), ((n,), BOOL), ((k,), I32), ((k,), BOOL))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", ["conflict_fused",
                                    "conflict_fused_full"])
def test_conflict_kernels_compile(one_chip, kernel):
    fn = lambda r, w: getattr(KC, kernel)(r, w, interpret=False)  # noqa: E731
    shape = ((N_TXN, TXN_WORDS), U32)
    hlo = _compile(fn, one_chip, shape, shape)
    assert "tpu_custom_call" in hlo
