"""Described-chip compiles: the Pallas kernels of the `pallas` sort_impl and
the serving tier's query kernel, compiled for a TPU v5e that is described,
not attached (nothing runs, so results are covered by the interpret-mode
parity tests). A kernel the chip's compiler refuses — unaligned blocks, a
gather Mosaic cannot lower — fails here instead of on the chip.

The topology is described inside a module fixture (never at import time),
so every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api.query import _ranges_kernel
from repro.kernels.bitonic_stage import bitonic_stage_pallas
from repro.kernels.radix_hist import radix_histogram_pallas
from repro.kernels.seg_boundary import seg_boundary_pallas


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described v5e:2x2, with the persistent compilation cache off
    (entries compiled for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield topo
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    """One chip of the described v5e:2x2."""
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _compile(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_radix_histogram_compiles(one_chip):
    hlo = _compile(lambda d: radix_histogram_pallas(d, 256, block=1024,
                                                    interpret=False),
                   one_chip, (1 << 16,))
    assert "tpu_custom_call" in hlo


def test_seg_boundary_compiles(one_chip):
    # dense ranking of the level-0 sample windows of a 2^18 build: v = 3
    hlo = _compile(lambda r: seg_boundary_pallas(r, block=512,
                                                 interpret=False),
                   one_chip, (1 << 17, 3))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("k,j", [(1 << 12, 64), (1 << 12, 1 << 11)],
                         ids=["in_tile", "cross_tile"])
def test_bitonic_stage_compiles(one_chip, k, j):
    # the window-row sort of a 2^18 build: 2^19 rows, v = 3 columns + index
    hlo = _compile(lambda r: bitonic_stage_pallas(r, k, j, tile=256,
                                                  interpret=False),
                   one_chip, (1 << 19, 4))
    assert "tpu_custom_call" in hlo


def test_ranges_kernel_compiles(one_chip):
    # the serving tier's query kernel over a 2^25-char index, B=64, L=64
    n = 1 << 25
    _compile(_ranges_kernel, one_chip, (n,), (n,), (64, 64), (64,))


@pytest.mark.parametrize("stage", ["sm1", "sm2"])
def test_bsp_stage_compiles(v5e_2x2, stage):
    # level 0 of BSP Algorithm 3 on a 2^16-character text over the four
    # chips; `tools/tpu_compile_probe.py sm1|sm2` times the same programs
    # at 2^25. A [rows, fields] layout that no longer fits the chip's
    # memory, or a scatter lowered through a sort, shows up here.
    from tools.tpu_compile_probe import lower_program
    _, lowered = lower_program(v5e_2x2, stage, n=(1 << 16) + 64)
    hlo = lowered.compile().as_text()
    assert hlo.count(" scatter(") <= (1 if stage == "sm1" else 0)
