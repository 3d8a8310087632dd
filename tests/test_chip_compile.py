"""Compile the Pallas kernels for a described TPU v5e, at real model widths.

No chip is needed: the TPU compiler compiles for a topology that is
described and not attached, and refuses what the chip would refuse
(misaligned blocks, more VMEM than a kernel may use).  Nothing runs, so
these tests say nothing about results or times.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one; keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, *args, **static):
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


STABLELM = get_config("stablelm_3b")
MAMBA2 = get_config("mamba2_370m")


@pytest.mark.parametrize(
    "phase,sq,skv",
    [("prefill", 2048, 2048), ("decode", 1, 4096)],
)
def test_flash_attention_compiles_at_stablelm_widths(one_chip, phase, sq, skv):
    h, dh = STABLELM.n_heads, STABLELM.dh  # BH = 32 at batch 1, dh = 80
    q = _spec(one_chip, (1, sq, h, dh), jnp.bfloat16)
    kv = _spec(one_chip, (1, skv, STABLELM.n_kv_heads, dh), jnp.bfloat16)
    qp = _spec(one_chip, (1, sq), jnp.int32)
    kp = _spec(one_chip, (1, skv), jnp.int32)
    _assert_kernel_compiles(ops.flash_attention, q, kv, kv, qp, kp)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_ssd_compiles_at_mamba2_widths(one_chip, dtype):
    s, h, p, n = 2048, MAMBA2.ssm_heads, MAMBA2.ssm_head_dim, MAMBA2.ssm_state
    _assert_kernel_compiles(
        ops.mamba2_ssd,
        _spec(one_chip, (1, s, h, p), dtype),
        _spec(one_chip, (1, s, h), dtype),
        _spec(one_chip, (h,), jnp.float32),
        _spec(one_chip, (1, s, n), dtype),
        _spec(one_chip, (1, s, n), dtype),
        chunk=MAMBA2.ssm_chunk,
    )
