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


def test_decode_step_holds_every_transformer_scope():
    found = _scopes_in(_decode_text(reduced_config("stablelm_3b")))
    assert found == {scopes.QKV, scopes.KV_WRITE, scopes.KV_CARRY, scopes.ATTENTION,
                     scopes.MLP, scopes.LM_HEAD}


def test_scopes_leave_the_compiled_decode_step_unchanged(monkeypatch):
    cfg = reduced_config("stablelm_3b")
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
    assert scopes.SSD in _scopes_in(text)
