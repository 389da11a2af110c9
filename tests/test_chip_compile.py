"""The main path's kernels compile for a TPU v5e at real sizes.

Nothing runs here: each test lowers and compiles one jitted piece of the
engine for a described (not attached) v5e chip, which raises whatever the
chip's compiler would refuse (a gather it cannot lower, a block that breaks
the tiling, a program over the chip's memory). The shapes are those of
``road_grid(2048, 2048)`` in 16 BFS-grown partitions: 262,144 vertex slots
per partition, an ELL width of 8, a mailbox capacity of 1,101 slots per
partition pair.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import megastep as mega
from repro.kernels import ops
from repro.kernels.ref import (outbox_pack_ref, semiring_spmv_frontier_ref,
                               semiring_spmv_ref)

P, V, D, CAP = 16, 262_144, 8, 1_101
M_LO, HUB_ROWS, M_HI = 3, 1, 8          # inbox feed-table widths
HBM_BYTES = 16 * 10**9                  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_fits(fn, *specs):
    ma = jax.jit(fn).lower(*specs).compile().memory_analysis()
    need = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert need < HBM_BYTES, f"needs {need} bytes of HBM"
    return ma


@pytest.mark.parametrize("semiring", ["min_plus", "max_first",
                                      "plus_times"])
def test_sweep_compiles_at_road_width(one_chip, semiring):
    _compile_fits(
        lambda x, nbr, wgt: semiring_spmv_ref(x, nbr, wgt, semiring),
        _spec((V,), jnp.float32, one_chip),
        _spec((V, D), jnp.int32, one_chip),
        _spec((V, D), jnp.float32, one_chip))


def test_requested_pallas_sweep_is_the_kernel_or_raises(one_chip):
    """Asking for the Pallas sweep gets the compiled Mosaic kernel or the
    chip compiler's error, never the interpreter or the jnp route."""
    fn = jax.jit(lambda x, nbr, wgt: ops.semiring_spmv(
        x, nbr, wgt, "min_plus", backend="pallas"))
    specs = (_spec((1024,), jnp.float32, one_chip),
             _spec((1024, D), jnp.int32, one_chip),
             _spec((1024, D), jnp.float32, one_chip))
    try:
        text = fn.lower(*specs).compile().as_text()
    except Exception as e:      # today's kernel: "Only 2D gather ..."
        assert "gather" in str(e) or "Mosaic" in str(e), e
        return
    assert "tpu_custom_call" in text


def test_frontier_sweep_compiles_vmapped_over_partitions(one_chip):
    sweep = jax.vmap(lambda x, f, nbr, wgt: semiring_spmv_frontier_ref(
        x, f, nbr, wgt, "min_plus"))
    _compile_fits(sweep,
                  _spec((P, V), jnp.float32, one_chip),
                  _spec((P, V), jnp.bool_, one_chip),
                  _spec((P, V, D), jnp.int32, one_chip),
                  _spec((P, V, D), jnp.float32, one_chip))


def test_outbox_pack_compiles_at_slot_geometry(one_chip):
    pack = jax.vmap(lambda sv, act, lim: outbox_pack_ref(sv, act, lim,
                                                         jnp.inf))
    _compile_fits(pack,
                  _spec((P, P, CAP), jnp.float32, one_chip),
                  _spec((P, P, CAP), jnp.bool_, one_chip),
                  _spec((P, P), jnp.int32, one_chip))


def test_megastep_superstep_compiles_on_flat_state(one_chip):
    n = P * V
    arrays = {
        "vmask": ((n,), jnp.bool_),
        "nbr": ((n, D), jnp.int32), "nbr_ok": ((n, D), jnp.bool_),
        "wgt": ((n, D), jnp.float32),
        "lo_src": ((n, M_LO), jnp.int32), "lo_ok": ((n, M_LO), jnp.bool_),
        "lo_w": ((n, M_LO), jnp.float32),
        "hub_src": ((P * HUB_ROWS, M_HI), jnp.int32),
        "hub_ok": ((P * HUB_ROWS, M_HI), jnp.bool_),
        "hub_w": ((P * HUB_ROWS, M_HI), jnp.float32),
        "hub_row": ((n,), jnp.int32), "hub_row_ok": ((n,), jnp.bool_),
    }

    def step(x, changed, frontier, cma):
        cm = dict(cma, num_parts=P, v_max=V, cap=CAP, n=n)
        return mega.megastep_semiring(x, changed, frontier, cm, "min_plus")

    _compile_fits(step,
                  _spec((n,), jnp.float32, one_chip),
                  _spec((n,), jnp.bool_, one_chip),
                  _spec((n,), jnp.bool_, one_chip),
                  {k: _spec(s, d, one_chip) for k, (s, d) in arrays.items()})
