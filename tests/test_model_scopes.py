"""The model's named scopes (``repro.models.scopes``) reach the compiled
steps' ``op_name`` metadata, and change nothing else in them."""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from chipbench import scopes as chipbench_scopes
from repro.configs import reduced_config
from repro.models import Model, scopes
from repro.runtime.serving import ServeConfig, Server

SLOTS, MAX_LEN = 2, 32


def _decode_text(cfg) -> str:
    """The compiled text of ``Server``'s decode step at test sizes, built as
    the benchmark builds it for its cells."""
    return chipbench_scopes.decode_text(cfg, SLOTS, MAX_LEN)


def _scopes_in(text: str):
    return {name for name in scopes.ALL
            if any(f"/{name}/" in op for op in re.findall(r'op_name="([^"]*)"', text))}


def _code(text: str) -> str:
    """The compiled text without metadata, source tables or name numbering."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    lines = [ln for ln in text.splitlines()
             if not re.match(r"^(FileNames|FunctionNames|FileLocations|StackFrames)$|^\d+ ", ln)]
    return re.sub(r"(%?[A-Za-z_][\w-]*)\.\d+\b", r"\1", "\n".join(lines))


@pytest.mark.parametrize("arch,expected", [
    ("stablelm_3b", {scopes.QKV, scopes.KV_WRITE, scopes.KV_CARRY, scopes.ATTENTION,
                     scopes.MLP, scopes.LM_HEAD}),
    ("zamba2_7b", {scopes.QKV, scopes.KV_WRITE, scopes.KV_CARRY, scopes.ATTENTION, scopes.MLP,
                   scopes.LM_HEAD, scopes.SSD, scopes.MAMBA}),
], ids=["transformer", "hybrid"])
def test_decode_step_holds_every_transformer_scope(arch, expected):
    assert _scopes_in(_decode_text(reduced_config(arch))) == expected


@pytest.mark.parametrize("arch", ["stablelm_3b", "zamba2_7b"])
def test_scopes_leave_the_compiled_decode_step_unchanged(monkeypatch, arch):
    cfg = reduced_config(arch)
    scoped = _decode_text(cfg)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _decode_text(cfg)
    assert not _scopes_in(plain)
    assert _code(scoped) == _code(plain)


@pytest.mark.parametrize("decode", [False, True], ids=["chunked", "decode_step"])
def test_mamba2_block_holds_the_ssd_scope(decode):
    cfg = reduced_config("mamba2_370m")
    model = Model(cfg)
    params = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 1 if decode else 16), jnp.int32)
    if decode:
        state = jax.eval_shape(lambda p, t: model.prefill(p, {"tokens": t}, MAX_LEN)[1],
                               params, jax.ShapeDtypeStruct((2, 8), jnp.int32))
        fn, args = jax.jit(model.decode_step), (params, tokens, state)
    else:
        fn = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, MAX_LEN))
        args = (params, tokens)
    text = fn.lower(*args).compile().as_text()
    assert {scopes.SSD, scopes.MAMBA} <= _scopes_in(text)


def _kernel_calls(cfg):
    """Lowered for a TPU (no chip needed to lower), ``Server``'s decode step
    for ``cfg``: {jitted kernel wrapper: (its kernel custom calls, the
    location of each call of it)}.  A wrapper's custom call sits in a
    function of its own, and the decode step calls that function where the
    scopes are."""
    params = Model(cfg).abstract_init()[0]
    server = Server(cfg, ServeConfig(batch_slots=SLOTS, max_len=256), params)
    one = jax.eval_shape(server._prefill_fn, params, jax.ShapeDtypeStruct((1, 1), jnp.int32))[1]
    state = Server._tree_map_batch(
        lambda x, ax: jax.ShapeDtypeStruct(x.shape[:ax] + (SLOTS,) + x.shape[ax + 1:], x.dtype),
        one)
    lowered = server._decode.trace(params, jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32),
                                   state).lower(lowering_platforms=("tpu",))
    calls, kernels = [], {}

    def walk(op, func):
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    name = inner.operation.name
                    if name == "func.func":
                        walk(inner.operation, str(inner.attributes["sym_name"]).strip('"'))
                        continue
                    if name == "func.call":
                        calls.append((str(inner.attributes["callee"]).lstrip("@"),
                                      str(inner.location)))
                    if (name == "stablehlo.custom_call"
                            and "tpu_custom_call" in str(inner.attributes["call_target_name"])):
                        kernels[func] = kernels.get(func, 0) + 1
                    walk(inner.operation, func)

    walk(lowered.compiler_ir("stablehlo").operation, None)
    assert None not in kernels, kernels
    return {func: (n, [loc for callee, loc in calls if callee == func])
            for func, n in kernels.items()}


def _scopes_on(loc: str):
    """The model's scopes on the name path of a call's location."""
    return [part for part in loc.split('"')[1].split("/") if part in scopes.ALL]


@pytest.mark.parametrize("arch", ["stablelm_3b", "zamba2_7b"])
def test_decode_kernel_falls_under_the_attention_scope(arch):
    """Lowered for a TPU, the decode step reads its caches through the
    decode-attention kernel, called once per layer (in the layer scan's
    body) or application, each call inside the ``attention`` scope: the
    benchmark's split puts its device time there."""
    cfg = reduced_config(arch)
    kernels = _kernel_calls(cfg)
    assert set(kernels) == {"decode_attention", "write_rows"}, kernels
    n, sites = kernels["decode_attention"]
    apps = len(cfg.hybrid_layer_ids) if cfg.family == "hybrid" else 1  # a scan body holds one
    assert n == 1 and len(sites) == apps, sites
    for loc in sites:
        assert _scopes_on(loc) == [scopes.ATTENTION], loc
        assert "decode_attention" in loc.split('"')[1], loc


@pytest.mark.parametrize("arch", ["stablelm_3b", "zamba2_7b"])
def test_kv_write_kernel_falls_under_the_kv_write_scope(arch):
    """Lowered for a TPU, the decode step writes every layer's or
    application's new rows with one call of the row-write kernel, inside the
    ``kv_write`` scope and no other: the benchmark's split puts its device
    time there."""
    n, sites = _kernel_calls(reduced_config(arch))["write_rows"]
    assert n == 1 and len(sites) == 1, sites
    assert _scopes_on(sites[0]) == [scopes.KV_WRITE], sites
