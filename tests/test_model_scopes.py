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


@pytest.mark.parametrize("arch", ["stablelm_3b", "zamba2_7b"])
def test_decode_kernel_falls_under_the_attention_scope(arch):
    """Lowered for a TPU (no chip needed to lower), the decode step reads its
    caches through the decode-attention kernel, called once per layer (in
    the layer scan's body) or application, each call inside the
    ``attention`` scope: the benchmark's split puts its device time there."""
    cfg = reduced_config(arch)
    params = Model(cfg).abstract_init()[0]
    server = Server(cfg, ServeConfig(batch_slots=SLOTS, max_len=256), params)
    one = jax.eval_shape(server._prefill_fn, params, jax.ShapeDtypeStruct((1, 1), jnp.int32))[1]
    state = Server._tree_map_batch(
        lambda x, ax: jax.ShapeDtypeStruct(x.shape[:ax] + (SLOTS,) + x.shape[ax + 1:], x.dtype),
        one)
    lowered = server._decode.trace(params, jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32),
                                   state).lower(lowering_platforms=("tpu",))

    # the kernel's wrapper is jitted: its custom call sits in a function of
    # its own, and the decode step calls that function where the scopes are
    calls, kernel_funcs = [], set()

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
                        kernel_funcs.add(func)
                    walk(inner.operation, func)

    walk(lowered.compiler_ir("stablehlo").operation, None)
    assert len(kernel_funcs) == 1 and None not in kernel_funcs, kernel_funcs
    sites = [loc for callee, loc in calls if callee in kernel_funcs]
    apps = len(cfg.hybrid_layer_ids) if cfg.family == "hybrid" else 1  # a scan body holds one
    assert len(sites) == apps, sites
    for loc in sites:
        path = loc.split('"')[1]
        assert path.split("/").count(scopes.ATTENTION) == 1, loc
        assert "decode_attention" in path, loc
