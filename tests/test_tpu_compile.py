"""The served Pallas kernels compile for a TPU v5e at real widths.

Compile only: the topology is described, not attached, so this runs on a
CPU-only machine and guards against kernels the TPU compiler refuses
(block shapes off its tiling, values it cannot trace) at the shapes the
two served tenants produce.  Nothing here runs a kernel.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import quant_matmul as qm
from repro.kernels import ssd_scan as ssd

GROUP = 32  # the serving zoo's quantization group (TenantRuntime)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache off.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (M, K, N): decode M=4 and prefill M>=44, on mamba2-780m's ssm_in
# (N=6448, not a multiple of the 256-wide block) and granite-3-2b's wd.
@pytest.mark.parametrize("M,K,N", [
    (4, 1536, 6448), (44, 1536, 6448),
    (4, 8192, 2048), (64, 8192, 2048),
])
def test_quant_matmul_compiles_for_v5e(one_chip, M, K, N):
    f = jax.jit(lambda x, w, s: qm.quant_matmul(x, w, s))
    lowered = f.lower(_spec((M, K), jnp.bfloat16, one_chip),
                      _spec((K, N), jnp.int8, one_chip),
                      _spec((K // GROUP, N), jnp.float32, one_chip))
    # The kernel carries its name into the program and the device trace.
    assert 'kernel_name = "quant_matmul"' in lowered.as_text()
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"%quant_matmul[.\d]* = .*custom-call\(", text)


# mamba2-780m: 48 heads of 64, one group of state 128, chunk 256; a
# 16-token prompt (one partial chunk) and a 600-token one (3 chunks).
@pytest.mark.parametrize("S", [16, 600])
def test_ssd_scan_compiles_for_v5e(one_chip, S):
    H, P, G, N = 48, 64, 1, 128
    f = jax.jit(lambda *a: ssd.ssd_scan(*a, chunk=256, return_state=True))
    lowered = f.lower(_spec((1, S, H, P), jnp.bfloat16, one_chip),
                      _spec((1, S, H), jnp.bfloat16, one_chip),
                      _spec((H,), jnp.float32, one_chip),
                      _spec((1, S, G, N), jnp.bfloat16, one_chip),
                      _spec((1, S, G, N), jnp.bfloat16, one_chip),
                      _spec((H,), jnp.float32, one_chip))
    assert 'kernel_name = "ssd_scan"' in lowered.as_text()
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"%ssd_scan[.\d]* = .*custom-call\(", text)
