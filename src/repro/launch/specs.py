"""Abstract input specs + sharding policies for every (arch x shape) cell.

``cell_inputs`` builds ShapeDtypeStruct stand-ins (no allocation) for the
inputs of each step kind; ``cell_shardings`` assigns NamedShardings:

* batch dims shard over the data axes (``pod`` x ``data``); a batch of 1
  (long_500k) leaves batch unsharded and puts the model axis on the KV/SSM
  sequence/state dims instead;
* KV caches shard heads over ``model`` when the head count divides the axis,
  else the cache *sequence* is sharded over ``model`` (GQA archs with few
  KV heads — exactness preserved, collectives appear in the roofline);
* SSM states shard their head dim over ``model`` when divisible.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ShapeCell
from repro.models import ModelConfig
from repro.models.mamba2 import D_CONV, mamba_dims
from repro.models import hybrid as hybrid_mod

S = jax.ShapeDtypeStruct


def data_axes(mesh: Mesh):
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return axes if len(axes) > 1 else (axes[0] if axes else None)


def model_axis_size(mesh: Mesh) -> int:
    return mesh.shape.get("model", 1)


# ---------------------------------------------------------------------------
# Shape-cell geometry per family
# ---------------------------------------------------------------------------


def cell_geometry(cfg: ModelConfig, cell: ShapeCell) -> Dict[str, int]:
    """Resolve the canonical (seq_len x batch) into per-family input dims."""
    g = {"batch": cell.global_batch, "seq": cell.seq_len, "n_patches": 0, "n_frames": 0}
    if cfg.family == "vlm":
        g["n_patches"] = 256  # fixed-resolution stub: 256 patch tokens prefix
    if cfg.family == "audio":
        g["n_frames"] = 1500  # 30 s of audio
        # the seq budget is split: 1500 encoder frames + decoder positions
        g["seq"] = max(cell.seq_len - 1500, 448 if cell.kind != "train" else 2048)
        if cell.kind == "train":
            g["seq"] = min(g["seq"], 4096)
    return g


# ---------------------------------------------------------------------------
# Abstract inputs per step kind
# ---------------------------------------------------------------------------


def train_inputs(cfg: ModelConfig, cell: ShapeCell) -> Dict[str, Any]:
    g = cell_geometry(cfg, cell)
    B, Sq = g["batch"], g["seq"]
    out = {
        "tokens": S((B, Sq), jnp.int32),
        "targets": S((B, Sq), jnp.int32),
    }
    if cfg.family == "vlm":
        out["patch_embeds"] = S((B, g["n_patches"], cfg.d_model), jnp.bfloat16)
        out["mrope_positions"] = S((B, Sq, 3), jnp.int32)
    if cfg.family == "audio":
        out["frame_embeds"] = S((B, g["n_frames"], cfg.d_model), jnp.bfloat16)
    return out


def prefill_inputs(cfg: ModelConfig, cell: ShapeCell) -> Dict[str, Any]:
    out = train_inputs(cfg, cell)
    out.pop("targets")
    return out


def decode_state_struct(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Abstract decode state matching Model.prefill's output structure."""
    st: Dict[str, Any] = {"pos": S((batch,), jnp.int32)}
    if cfg.family in ("dense", "moe", "vlm"):
        kv = S((cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.dh), jnp.bfloat16)
        st["kv"] = (kv, kv)
    elif cfg.family == "ssm":
        d_inner, conv_dim = mamba_dims(cfg.d_model, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                                       cfg.ssm_groups)
        st["ssm"] = S((cfg.n_layers, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)
        st["conv"] = S((cfg.n_layers, batch, D_CONV - 1, conv_dim), jnp.bfloat16)
    elif cfg.family == "hybrid":
        st.update(jax.eval_shape(lambda: hybrid_mod.init_state(cfg, batch, max_len)))
    elif cfg.family == "audio":
        kv = S((cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.dh), jnp.bfloat16)
        st["kv"] = (kv, kv)
        st["enc"] = S((batch, 1500, cfg.d_model), cfg.dtype)
    return st


def decode_inputs(cfg: ModelConfig, cell: ShapeCell) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    g = cell_geometry(cfg, cell)
    B = g["batch"]
    max_len = g["seq"] if cfg.family != "audio" else max(g["seq"], 448)
    # pad the cache length to a multiple of 1024 so a model-axis-sharded
    # sequence dim always divides (e.g. whisper's 31268-token budget)
    max_len = -(-max_len // 1024) * 1024
    tokens = S((B, 1), jnp.int32)
    return {"tokens": tokens}, decode_state_struct(cfg, B, max_len)


# ---------------------------------------------------------------------------
# Shardings
# ---------------------------------------------------------------------------


def _dp_for_batch(mesh: Mesh, batch: int):
    dp = data_axes(mesh)
    if dp is None:
        return None
    size = int(np.prod([mesh.shape[a] for a in (dp if isinstance(dp, tuple) else (dp,))]))
    return dp if batch % size == 0 and batch >= size else None


def batch_shardings(mesh: Mesh, inputs: Dict[str, Any], batch: int) -> Dict[str, Any]:
    dp = _dp_for_batch(mesh, batch)

    def shard(leaf):
        return NamedSharding(mesh, P(*([dp] + [None] * (len(leaf.shape) - 1))))

    return jax.tree.map(shard, inputs)


def state_shardings(cfg: ModelConfig, mesh: Mesh, state: Dict[str, Any], batch: int) -> Dict[str, Any]:
    dp = _dp_for_batch(mesh, batch)
    ms = model_axis_size(mesh)
    heads_shardable = cfg.n_kv_heads > 0 and cfg.n_kv_heads % ms == 0
    ssm_shardable = cfg.ssm_heads > 0 and cfg.ssm_heads % ms == 0
    # batch=1 (long_500k): put every mesh axis on the sequence/state dims
    seq_axes: Any = "model" if dp is not None else tuple(
        a for a in ("pod", "data", "model") if a in mesh.axis_names
    )

    out: Dict[str, Any] = {}
    for key, leaf in state.items():
        if key == "pos":
            out[key] = NamedSharding(mesh, P(dp))
        elif key == "kv":
            if heads_shardable:
                spec = P(None, dp, None, "model", None)
            else:
                spec = P(None, dp, seq_axes, None, None)
            out[key] = (NamedSharding(mesh, spec), NamedSharding(mesh, spec))
        elif key == "ssm":  # one array, or the hybrid's one per segment
            spec = P(None, dp, "model" if ssm_shardable else None, None, None)
            out[key] = jax.tree.map(lambda _: NamedSharding(mesh, spec), leaf)
        elif key == "conv":
            out[key] = jax.tree.map(lambda _: NamedSharding(mesh, P(None, dp, None, "model")), leaf)
        elif key == "enc":
            out[key] = NamedSharding(mesh, P(dp, None, None))
        else:  # pragma: no cover
            out[key] = NamedSharding(mesh, P())
    return out
