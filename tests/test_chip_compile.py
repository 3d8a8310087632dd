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


def _cache_shapes(stack: int, slots: int, max_len: int, hkv: int, dh: int):
    """A stack of ``stack`` caches, and one entry or slot of it, as the model
    holds them ``(.., slots, max_len, hkv, dh)`` and as the decode kernel
    reads them ``(.., slots, hkv, dh, max_len)``."""
    out = set()
    for entry in ((slots, max_len, hkv, dh), (slots, hkv, dh, max_len)):
        out |= {entry, (1,) + entry, (stack,) + entry}
    return out


def _moved(text: str, shapes) -> list:
    """The instructions that copy, select, scatter or transpose into one of
    ``shapes``."""
    moved = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group(3) in ("copy", "select", "scatter", "transpose"):
            if tuple(int(d) for d in m.group(2).split(",") if d) in shapes:
                moved.append(line.strip()[:160])
    return moved


def _instructions(text: str):
    """Instruction name -> (computation, opcode, operand names, line), and
    the name of the entry computation."""
    out, computation, entry = {}, None, None
    for line in text.splitlines():
        if not line.startswith(" "):
            head = re.match(r"^(ENTRY\s+)?%?([^\s(]+).*\{\s*$", line)
            if head:
                computation = head.group(2)
                entry = computation if head.group(1) else entry
            continue
        m = re.match(r"^\s+(?:ROOT\s+)?%?([^\s=]+) = (.*)$", line)
        if not m:
            continue
        name, rest = m.groups()
        if rest.startswith("("):  # a tuple's shape
            depth = 0
            for end, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if not depth:
                    break
            rest = rest[end + 1:].lstrip()
        else:
            rest = rest.split(" ", 1)[1]
        op = re.match(r"([\w-]+)\(", rest)
        depth, args = 1, op.end()
        for end in range(op.end(), len(rest)):
            depth += (rest[end] == "(") - (rest[end] == ")")
            if not depth:
                args = rest[op.end():end]
                break
        out[name] = (computation, op.group(1), re.findall(r"%([\w.\-]+)", args), line)
    return out, entry


def _entry_parameter(instructions, entry: str, name: str):
    """The entry computation's parameter that instruction ``name`` is,
    through bitcasts and the tuples of the loops it is in; None where some
    instruction on the way makes a new value."""
    loops = {}  # loop body -> the tuple a while passes it
    for computation, op, operands, line in instructions.values():
        body = re.search(r"\bbody=%?([^\s,]+)", line)
        if op == "while" and body:
            loops[body.group(1)] = operands[0]
    while True:
        computation, op, operands, line = instructions[name]
        if op == "bitcast":
            name = operands[0]
        elif op == "parameter":
            return name if computation == entry else None
        elif op == "get-tuple-element":
            source = instructions[operands[0]]
            if source[1] != "parameter" or source[0] not in loops:
                return None
            passed = instructions[loops[source[0]]]
            if passed[1] != "tuple":
                return None
            name = passed[2][int(re.search(r"\bindex=(\d+)", line).group(1))]
        else:
            return None


def _assert_kernel_reads_the_stacked_caches(text: str, stack_shape, kernels: int):
    """``kernels`` decode-attention custom calls, each in the ``attention``
    scope, whose cache operands are the step's stacked cache parameters
    themselves, seen as the kernel reads them: no copy between."""
    from chipbench.scopes import instruction_scopes
    from repro.models import scopes

    instructions, entry = _instructions(text)
    calls = {name: operands for name, (_, op, operands, line) in instructions.items()
             if op == "custom-call" and "tpu_custom_call" in line and "decode_attention" in line}
    assert len(calls) == kernels, sorted(calls)
    in_scope = instruction_scopes(text, scopes.ALL)
    shape = "bf16[%s]" % ",".join(map(str, stack_shape))
    for name, operands in calls.items():
        assert in_scope[name] == scopes.ATTENTION, name
        caches = [_entry_parameter(instructions, entry, o) for o in operands[-2:]]
        assert all(c is not None and shape in instructions[c][3] for c in caches), (name, caches)
        assert len(set(caches)) == 2, caches


def test_serving_decode_step_moves_no_cache_sized_data(one_chip):
    """``Server``'s decode step at the stablelm_3b serving cell's sizes (12
    slots x 2048, full widths) reads each layer's cache where it is stored,
    through the decode-attention kernel, and writes only the token's rows:
    the kernel takes the stacked cache parameters themselves, in the
    ``attention`` scope, and no copy, select, scatter or transpose yields a
    layer's slice or the stacked cache, as the model holds it or as the
    kernel reads it.  The step needs under 64 MiB of temporaries beside its
    arguments."""
    cell = Cell.load("stablelm_3b.decode_heavy")
    cfg = program_config(cell.config)
    slots, max_len = int(cell.config["serve"]["slots"]), int(cell.config["serve"]["max_len"])
    compiled = _compile_decode_step(one_chip, cfg, slots, max_len)
    text = compiled.as_text()

    moved = _moved(text, _cache_shapes(cfg.n_layers, slots, max_len, cfg.n_kv_heads, cfg.dh))
    assert not moved, moved
    _assert_kernel_reads_the_stacked_caches(
        text, (cfg.n_layers, slots, max_len, cfg.n_kv_heads, cfg.dh), kernels=1)
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
    state and its temporaries in 15.75 GiB, reads each
    application's cache where it is stored, through the decode-attention
    kernel (one custom call an application, in the ``attention`` scope,
    taking the stacked cache parameters themselves), and carries each
    segment's SSM state in the layout it is stored in: no copy, select,
    scatter or transpose yields an application's cache or the stacked
    caches, as the model holds them or as the kernel reads them, or a
    segment's SSM state.  A
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

    apps = len(cfg.hybrid_layer_ids)
    moved_shapes = _cache_shapes(apps, slots, max_len, cfg.n_kv_heads, cfg.dh)
    moved_shapes |= {(cfg.n_layers, slots, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)}
    moved_shapes |= {(hi - lo, slots, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
                     for _, lo, hi in hybrid.segments(cfg)}
    text = compiled.as_text()
    moved = _moved(text, moved_shapes)
    assert not moved, moved
    _assert_kernel_reads_the_stacked_caches(
        text, (apps, slots, max_len, cfg.n_kv_heads, cfg.dh), kernels=apps)
    # 399.5 MiB before the kernel, which adds its outputs and the queries' view
    assert mem.temp_size_in_bytes < 401 * 2**20, mem.temp_size_in_bytes / 2**20

    params = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                          Model(cfg).abstract_init()[0])
    server = Server(cfg, ServeConfig(batch_slots=slots, max_len=max_len), params)
    prefill = server._prefill.lower(params, _spec(one_chip, (1, 1920), jnp.int32)).compile()
    pm = prefill.memory_analysis()
    assert (pm.argument_size_in_bytes + pm.output_size_in_bytes + pm.temp_size_in_bytes
            + state_bytes) < hbm
