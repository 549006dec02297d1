"""Ahead-of-time compiles of the main-path device programs for a TPU v5e.

The chip is described, not attached: jax's TPU compiler lowers each
program for one v5e core and raises whatever the chip's compiler would
(Mosaic block rules, unsupported primitives, float64 inside a kernel).
Nothing runs, so these say nothing about results or speed; they guard
against code that only ever passed in interpret mode.  Where no TPU
compiler is installed the fixture skips every case.

The topology is described inside the module fixture, never at import:
only one process may load the TPU library at a time.
"""

import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import maxplus_bellman as kbell
from repro.kernels.lif_crossbar import lif_crossbar_step
from repro.kernels.maxplus_matmul import maxplus_bmm, maxplus_bmv, maxplus_matmul

#: (B, n, d) of the largest ELL packs a CPU rehearsal of ``chip_smoke.py``
#: dispatched (csr-jit forced): phase (c), the 224-tenant burst on the
#: 32x32 chip, and phase (b), the eight full-size Table-1 apps
ELL_PACKS = {"burst": (64, 192, 64), "table1": (96, 1024, 64)}

#: (B, n, d) of the shared-topology packs of the Table-1 admissions: the
#: 64 candidate bindings of HeartClass and of LeNet-CIFAR
SHARED_PACKS = {"heartclass": (64, 1024, 32), "lenet_cifar": (64, 768, 64)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's executables cannot be read back from a
        # persistent cache, so keep any configured cache out of the way
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _compile_bisect(sharding, nodes, d, reps, b, n):
    """``_csr_bisect`` as ``mcr_bisect_device`` dispatches it (inside a
    ``jax.enable_x64`` scope), for ELL operands of ``nodes`` nodes with
    ``reps`` replicas each."""
    with jax.enable_x64(True):
        args = (
            (
                _spec(sharding, (nodes, d), jnp.int32),
                _spec(sharding, (nodes, d, reps), jnp.float64),
                _spec(sharding, (nodes, d), jnp.float64),
            ),
            _spec(sharding, (b,), jnp.float64),
            _spec(sharding, (b,), jnp.float64),
            _spec(sharding, (b,), jnp.bool_),
            _spec(sharding, (), jnp.float64),
        )
        return kbell._csr_bisect.lower(
            *args, n_actors=n, k_probes=kbell.DEFAULT_K_PROBES,
            max_steps=40, max_rounds=0, detect_deadlock=False,
        ).compile()


@pytest.mark.parametrize("pack", sorted(ELL_PACKS))
def test_csr_bisect_ell_compiles(one_chip, pack):
    """The exact float64 solve of a per-row pack at the smoke run's
    packs."""
    b, n, d = ELL_PACKS[pack]
    compiled = _compile_bisect(one_chip, b * n, d, 1, b, n)
    mem = compiled.memory_analysis()
    # the whole solve stays well inside one v5e's 16 GB of HBM
    assert mem.temp_size_in_bytes < 4 * 1024**3, mem


@pytest.mark.parametrize("pack", sorted(SHARED_PACKS))
def test_csr_bisect_shared_compiles(one_chip, pack):
    """The solve of a shared-topology pack relaxes node-major: each edge
    slot gathers one row of every replica's probe distances, not a
    3-wide slice per (row, node)."""
    b, n, d = SHARED_PACKS[pack]
    compiled = _compile_bisect(one_chip, n, d, b, b, n)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4 * 1024**3, mem
    widths = {int(sizes.split(",")[-1]) for sizes in re.findall(
        r"slice_sizes=\{([0-9,]+)\}", compiled.as_text())}
    # the pointer hops gather single elements; the relaxation gathers
    # rows of B * K distances
    assert widths - {1} == {b * kbell.DEFAULT_K_PROBES}, widths


@pytest.mark.parametrize("shape", [(256, 128, 384), (128, 256, 128)])
def test_maxplus_matmul_compiles(one_chip, shape):
    m, k, n = shape
    compiled = jax.jit(maxplus_matmul).lower(
        _spec(one_chip, (m, k)), _spec(one_chip, (k, n))
    ).compile()
    _assert_kernel(compiled)


def test_maxplus_bmm_compiles(one_chip):
    compiled = jax.jit(maxplus_bmm).lower(
        _spec(one_chip, (4, 256, 128)), _spec(one_chip, (4, 128, 256))
    ).compile()
    _assert_kernel(compiled)


def test_maxplus_bmv_compiles(one_chip):
    compiled = jax.jit(maxplus_bmv).lower(
        _spec(one_chip, (8, 256, 384)), _spec(one_chip, (8, 384))
    ).compile()
    _assert_kernel(compiled)


def test_lif_crossbar_step_compiles(one_chip):
    compiled = jax.jit(lif_crossbar_step).lower(
        _spec(one_chip, (8, 256)),
        _spec(one_chip, (256, 128)),
        _spec(one_chip, (8, 128)),
    ).compile()
    _assert_kernel(compiled)

