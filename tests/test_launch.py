"""Launch helpers: mesh axis types and the persistent compile cache path."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh


def test_host_mesh_axes_are_auto():
    mesh = make_host_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_gather_from_vocab_sharded_embedding_traces_on_host_mesh():
    """``embed[tokens]`` with the table sharded over ``model`` and the
    tokens over ``data``: under explicitly typed mesh axes (the
    ``jax.make_mesh`` default) this gather is a sharding type error."""
    mesh = make_host_mesh()
    embed = jax.ShapeDtypeStruct(
        (256, 64), jnp.bfloat16, sharding=NamedSharding(mesh, P("model", None))
    )
    tokens = jax.ShapeDtypeStruct(
        (4, 32), jnp.int32, sharding=NamedSharding(mesh, P("data", None))
    )
    out = jax.jit(lambda e, t: e[t]).trace(embed, tokens).out_info
    assert out.shape == (4, 32, 64)


def test_host_mesh_over_chosen_devices():
    mesh = make_host_mesh(model=4, devices=jax.devices()[:1])
    assert dict(mesh.shape) == {"data": 1, "model": 1}


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert (compile_cache.REPO_ROOT / "chip_smoke.py").is_file()


@pytest.mark.parametrize("set_dir", [True, False])
def test_compile_cache_lands_where_the_variable_says(tmp_path, set_dir):
    """A fresh process: with the variable set, compiled programs land in
    that directory and the helper sets nothing; without it, the helper
    points JAX at the checkout's fixed path (checked, not written)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([str(compile_cache.REPO_ROOT / "src"), env.get("PYTHONPATH", "")])
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    cache = tmp_path / "cache"
    if set_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "path = enable_compile_cache()\n"
        "print(path, jax.config.jax_compilation_cache_dir)\n"
        "if jax.config.jax_compilation_cache_dir != path: raise SystemExit(1)\n"
    )
    if set_dir:
        code += (
            "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
        )
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=120
    )
    assert proc.returncode == 0, proc.stderr
    path = proc.stdout.split()[0]
    if set_dir:
        assert path == str(cache)
        assert any(cache.iterdir())
    else:
        assert path == str(compile_cache.REPO_ROOT / ".jax_cache")
