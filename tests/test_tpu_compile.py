"""Compile the simulator's device path for a described TPU v5e.

Interpret mode checks none of what the TPU compiler enforces — block
tiling, scalar stores to VMEM, what Mosaic can lower — so these compile
the Pallas ``lindley_scan`` kernel and the f64 ``jax`` scan at real sizes
against a ``v5e:2x2`` topology described on the host (no chip needed).
Nothing runs; a passing compile is not a chip run. The topology is only
described inside the fixture, never at import: one process at a time
may load the TPU library.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    # only an install without the TPU compiler skips; any other failure
    # to describe the topology fails the tests
    pytest.importorskip("libtpu", reason="no TPU compiler (libtpu) here")
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)


@pytest.mark.parametrize("shape", [(1, 1 << 20), (8, 1 << 17)])
def test_lindley_scan_compiles_for_v5e(one_chip, shape):
    from repro.kernels.lindley_scan import lindley_scan
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = lindley_scan.lower(x, x, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_jax_f64_scan_compiles_for_v5e(one_chip):
    from repro.core.sim_scan import _chunk_width, _jax_scan_fn
    npad = 1 << 16
    width = _chunk_width(npad)
    with jax.enable_x64(True):
        x = jax.ShapeDtypeStruct((1, width), jnp.float64, sharding=one_chip)
        compiled = _jax_scan_fn().lower(*[x] * (npad // width)).compile()
        out = compiled.out_info
    # the waits come back as chunks of one (1, 2^16) f64 row
    assert all(o.dtype == jnp.float64 for o in out)
    assert {o.shape[0] for o in out} == {1}
    assert sum(o.shape[-1] for o in out) == 1 << 16
