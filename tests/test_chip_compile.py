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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chipbench.bench import Cell
from chipbench.kinds.serve import program_config
from repro.configs import get_config
from repro.kernels import ops
from repro.models import Model
from repro.runtime.serving import ServeConfig, Server


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


_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def _compile_decode_step(sharding, cfg, slots, max_len):
    """``Server``'s decode step for ``cfg`` at ``slots`` x ``max_len``."""
    def on_chip(tree):
        return jax.tree.map(lambda x: _spec(sharding, x.shape, x.dtype), tree)

    params = on_chip(Model(cfg).abstract_init()[0])
    server = Server(cfg, ServeConfig(batch_slots=slots, max_len=max_len), params)
    one = jax.eval_shape(server._prefill_fn, params, jax.ShapeDtypeStruct((1, 1), jnp.int32))[1]
    state = on_chip(Server._tree_map_batch(
        lambda x, ax: jax.ShapeDtypeStruct(x.shape[:ax] + (slots,) + x.shape[ax + 1:], x.dtype),
        one))
    tokens = _spec(sharding, (slots, 1), jnp.int32)
    return server._decode.lower(params, tokens, state).compile()


def test_serving_decode_step_moves_no_cache_sized_data(one_chip):
    """``Server``'s decode step at the stablelm_3b serving cell's sizes (12
    slots x 2048, full widths) reads each layer's cache slice where it is
    stored and writes only the token's rows: no copy, select, scatter or
    transpose yields a layer's slice or the stacked cache, and the step
    needs under 64 MiB of temporaries beside its arguments."""
    cell = Cell.load("stablelm_3b.decode_heavy")
    cfg = program_config(cell.config)
    slots, max_len = int(cell.config["serve"]["slots"]), int(cell.config["serve"]["max_len"])
    compiled = _compile_decode_step(one_chip, cfg, slots, max_len)

    slice_dims = (slots, max_len, cfg.n_kv_heads, cfg.dh)
    cache_sized = {slice_dims, (1,) + slice_dims, (cfg.n_layers,) + slice_dims}
    moved = []
    for line in compiled.as_text().splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group(3) in ("copy", "select", "scatter", "transpose"):
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            if dims in cache_sized:
                moved.append(line.strip()[:160])
    assert not moved, moved
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_decode_step_compiles_for_gqa_at_head_size_128(one_chip):
    """qwen2_7b's decode step (28 query heads over 4 KV heads of 128) at full
    widths, on a small cache: the per-slot write of the new rows compiles
    where a rolled loop of it hit an internal error of the TPU compiler."""
    compiled = _compile_decode_step(one_chip, get_config("qwen2_7b"), 4, 256)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_zamba2_cell_compiles_and_fits(one_chip):
    """The zamba2_7b serving cell (27 layers at published widths, 24 slots x
    2048) on one described v5e.  Its decode step holds the weights, the
    state and its temporaries in 15.75 GiB, reads each application's cache
    where it is stored and carries each segment's SSM state in the layout
    it is stored in: no copy, select, scatter or transpose yields an
    application's cache, the stacked caches or a segment's SSM state.  A
    1920-token prefill fits beside the weights and the batch state the
    server holds while it runs."""
    from chipbench.kinds.serve_zamba2 import program_config as zamba2_config
    from repro.models import hybrid

    cell = Cell.load("zamba2_7b.decode_heavy_24")
    cfg = zamba2_config(cell.config)
    slots, max_len = int(cell.config["serve"]["slots"]), int(cell.config["serve"]["max_len"])
    hbm = 15.75 * 2**30
    compiled = _compile_decode_step(one_chip, cfg, slots, max_len)
    mem = compiled.memory_analysis()
    state_bytes = mem.output_size_in_bytes  # the new state, aliased to the donated one
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes)
    assert held < hbm, held / 2**30

    cache = (slots, max_len, cfg.n_kv_heads, cfg.dh)
    apps = len(cfg.hybrid_layer_ids)
    moved_shapes = {cache, (1,) + cache, (apps,) + cache, (cfg.n_layers, slots, cfg.ssm_heads,
                                                          cfg.ssm_head_dim, cfg.ssm_state)}
    moved_shapes |= {(hi - lo, slots, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
                     for _, lo, hi in hybrid.segments(cfg)}
    moved = []
    for line in compiled.as_text().splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group(3) in ("copy", "select", "scatter", "transpose"):
            if tuple(int(d) for d in m.group(2).split(",") if d) in moved_shapes:
                moved.append(line.strip()[:160])
    assert not moved, moved

    params = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                          Model(cfg).abstract_init()[0])
    server = Server(cfg, ServeConfig(batch_slots=slots, max_len=max_len), params)
    prefill = server._prefill.lower(params, _spec(one_chip, (1, 1920), jnp.int32)).compile()
    pm = prefill.memory_analysis()
    assert (pm.argument_size_in_bytes + pm.output_size_in_bytes + pm.temp_size_in_bytes
            + state_bytes) < hbm
