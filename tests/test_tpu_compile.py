"""Ahead-of-time compiles of the production kernels for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a topology described in
the fixture below and raises what Mosaic would raise on the chip — an
unlowerable primitive, an illegal block shape, a kernel over its VMEM
request. Each case compiles one kernel at a real size of its tier. Nothing
runs, so these say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.bitplane import BitPlanes
from repro.kernels import bitplane_field, common, ops, sweep


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # The TPU compiler logs under /tmp unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """ShapeDtypeStruct factory placed on one chip of the topology."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)


def _planes(shape, n, align_words=1, num_planes=1):
    w = common.round_up(-(-n // 32), align_words)
    return BitPlanes(shape((num_planes, n, w), jnp.uint32),
                     shape((num_planes, n, w), jnp.uint32), n)


def _store(shape, coupling, n):
    if coupling == "dense":
        return shape((n, n))
    return _planes(shape, n, 128 if coupling == "bitplane_hbm" else 1)


def _compiled_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


SWEEPS = {
    # id: (coupling, N, R, mode, with PWL table)
    "dense-2000-rsa": ("dense", 2000, 8, "rsa", False),
    "dense-2000-rwa": ("dense", 2000, 8, "rwa", False),
    "dense-2000-rsa-pwl": ("dense", 2000, 8, "rsa", True),
    "dense-2000-rwa-pwl": ("dense", 2000, 8, "rwa", True),  # k2000.*
    "dense-2000-rwa-r16": ("dense", 2000, 16, "rwa", False),
    "bitplane-8000-rsa": ("bitplane", 8000, 8, "rsa", False),
    "bitplane-7000-rwa": ("bitplane", 7000, 8, "rwa", False),
    "bitplane-7000-rwa-pwl": ("bitplane", 7000, 8, "rwa", True),   # g62.rwa
    "hbm-16384-rsa": ("bitplane_hbm", 16384, 8, "rsa", False),
    "hbm-16384-rwa": ("bitplane_hbm", 16384, 8, "rwa", False),
}


@pytest.mark.parametrize("case", sorted(SWEEPS))
def test_mcmc_sweep_compiles_for_v5e(shape, case):
    coupling, n, r, mode, pwl = SWEEPS[case]
    t = 256
    compiled = sweep.mcmc_sweep.lower(
        _store(shape, coupling, n), shape((r, n)), shape((r, n)),
        shape((r,)), shape((t, r, 4)), shape((t, r)),
        shape((65, 3)) if pwl else None, mode=mode, coupling=coupling,
        interpret=False).compile()
    assert _compiled_kernel(compiled)


COLORED = {
    # id: (coupling, N, R, class window)
    "bitplane-7056": ("bitplane", 7056, 8, 3584),
    "hbm-16384": ("bitplane_hbm", 16384, 8, 2048),
    "dense-1000-r16": ("dense", 1000, 16, 512),
}


@pytest.mark.parametrize("case", sorted(COLORED))
def test_colored_sweep_compiles_for_v5e(shape, case):
    coupling, n, r, win = COLORED[case]
    t = 256
    compiled = sweep.colored_sweep.lower(
        _store(shape, coupling, n), shape((r, n)), shape((r, n)),
        shape((r,)), shape((t, r, win)), shape((t, r)),
        shape((t, 3), jnp.int32), None, coupling=coupling,
        interpret=False).compile()
    assert _compiled_kernel(compiled)


@pytest.mark.parametrize("n", [2000, 7000])
def test_local_field_init_compiles_for_v5e(shape, n):
    fn = jax.jit(lambda s, j, h: ops.local_field_init(s, j, h,
                                                      interpret=False))
    compiled = fn.lower(shape((8, n)), shape((n, n)), shape((n,))).compile()
    assert _compiled_kernel(compiled)


@pytest.mark.parametrize("n,align", [(2000, 1), (7000, 1), (16384, 128)])
def test_bitplane_field_init_compiles_for_v5e(shape, n, align):
    planes = _planes(shape, n, align)
    fn = jax.jit(lambda p, q, x: bitplane_field.bitplane_field_init(
        p, q, x, interpret=False))
    compiled = fn.lower(planes.pos, planes.neg,
                        shape((8, planes.num_words), jnp.uint32)).compile()
    assert _compiled_kernel(compiled)
