"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch x shape x mesh) cell, from the compiled per-device module:

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = wire_bytes_per_device / link_bw

(The per-device framing is equivalent to the global/chips form since the
dry-run records the SPMD-partitioned per-device module, with scans unrolled
so loop bodies are counted the correct number of times.)

Also reports MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE) per device and
the usefulness ratio MODEL_FLOPS / HLO_FLOPs, plus the dominant term and a
one-line "what would move it" note.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

from repro.configs import active_param_count, get_config, param_count, shape_cells
from repro.launch.specs import cell_geometry

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
LINK_BW = 50e9               # bytes/s per ICI link

RESULTS_FILE = os.environ.get("DRYRUN_RESULTS", "dryrun_results.json")


def chips(mesh: str) -> int:
    return 512 if mesh == "2x16x16" else 256


def model_flops_cell(arch: str, cell_name: str) -> float:
    """Global MODEL_FLOPS for one cell (6ND train, 2ND prefill/decode +
    attention/SSD terms), before dividing by chips."""
    cfg = get_config(arch)
    cell = next(c for c in shape_cells(arch) if c.name == cell_name)
    g = cell_geometry(cfg, cell)
    B, S = g["batch"], g["seq"]
    n = active_param_count(cfg) if cfg.moe else param_count(cfg)

    def attn_flops(tokens: int, kv_len: int, causal: bool) -> float:
        if cfg.n_heads == 0:
            return 0.0
        per_layer = 2 * 2 * tokens * kv_len * cfg.n_heads * cfg.dh
        if causal:
            per_layer *= 0.5
        return per_layer * cfg.n_layers

    if cell.kind == "train":
        flops = 6 * n * B * S + 3 * attn_flops(B * S, S, True)
        if cfg.family == "audio":
            flops += 3 * attn_flops(B * g["n_frames"], g["n_frames"], False)
    elif cell.kind == "prefill":
        flops = 2 * n * B * S + attn_flops(B * S, S, True)
    else:  # decode: one token per sequence against the full context
        flops = 2 * n * B + attn_flops(B, S, False)
    return flops


def load_results(path: str = RESULTS_FILE) -> List[Dict[str, Any]]:
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# Analytic per-device traffic model
# ---------------------------------------------------------------------------
# The rolled dry-run counts while-loop bodies once, so flops / bytes /
# collectives for scanned programs come from this explicit model instead;
# it is validated against the fully-unrolled HLO measurements on the
# calibration cells (EXPERIMENTS.md §Roofline, "calibration").


def analytic_cell(arch: str, cell_name: str, mesh: str,
                  remat: str = "full", fsdp: bool = True) -> Dict[str, float]:
    cfg = get_config(arch)
    cell = next(c for c in shape_cells(arch) if c.name == cell_name)
    g = cell_geometry(cfg, cell)
    B, S = g["batch"], g["seq"]
    nchips = chips(mesh)
    tp = 16
    dp = nchips // tp
    n_total = param_count(cfg)
    n_active = active_param_count(cfg) if cfg.moe else n_total
    tokens = B * S if cell.kind != "decode" else B
    tok_dev = max(tokens // nchips, 1) if cell.kind != "decode" else max(B // dp, 1)

    # ---- FLOPs per device ---------------------------------------------------
    mf_global = model_flops_cell(arch, cell_name)
    remat_factor = {"none": 1.0, "dots": 1.1, "full": 4.0 / 3.0}[remat] if cell.kind == "train" else 1.0
    flops_dev = mf_global * remat_factor / nchips

    # ---- HBM bytes per device ------------------------------------------------
    D, L = cfg.d_model, cfg.n_layers
    act_bytes_layer = tok_dev * D * 2  # one activation tensor, bf16
    n_tensors = 14 if cell.kind == "train" else 5  # fwd(+bwd+remat) traffic
    if cell.kind == "train" and remat == "full":
        n_tensors += 6
    act_traffic = act_bytes_layer * n_tensors * L
    p_shard = n_active / tp / (dp if fsdp and cell.kind == "train" else 1)
    if cell.kind == "train":
        # p(bf16) rw + grad(f32) rw + mu/nu(f32) rw  (microbatch reuse ignored)
        param_traffic = p_shard * (2 * 2 + 2 * 4 + 4 * 4)
    else:
        param_traffic = (n_active / tp) * 2  # weights read once per step
    cache_traffic = 0.0
    if cell.kind == "decode" and cfg.n_heads:
        kv_total = 2 * cfg.n_layers * B * S * cfg.n_kv_heads * cfg.dh * 2
        if cfg.family == "hybrid":
            kv_total = 2 * len(cfg.hybrid_layer_ids) * B * S * cfg.n_kv_heads * cfg.dh * 2
        cache_traffic = kv_total / nchips
    hbm_dev = act_traffic + param_traffic + cache_traffic

    # ---- collective wire bytes per device ------------------------------------
    wire = 0.0
    if cfg.n_heads or cfg.family in ("ssm", "hybrid"):
        # TP: 2 all-reduces of the activation per layer (ring: ~2x size)
        wire += 2 * 2 * act_bytes_layer * L * (tp - 1) / tp
    if cell.kind == "train":
        if fsdp:
            # per-layer param all-gather fwd+bwd + grad reduce-scatter
            wire += 3 * (n_active / tp / dp) * 2 * (dp - 1)
        else:
            wire += 2 * (n_active / tp / dp) * 4 * (dp - 1) / dp  # grad all-reduce
    return {
        "flops": flops_dev,
        "bytes_accessed": hbm_dev,
        "wire_bytes": wire,
        "model_flops_per_chip": mf_global / nchips,
    }


def analyze(rec: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if rec.get("status") != "ok":
        return None
    nchips = chips(rec["mesh"])
    if rec.get("mode") == "unrolled":
        # fully-unrolled HLO: measured numbers are loop-complete
        flops = rec["flops"]
        hbm = rec["bytes_accessed"]
        wire = rec["collectives"].get("wire_bytes", rec["collectives"]["total_bytes"])
        src = "hlo"
    else:
        a = analytic_cell(
            rec["arch"], rec["shape"], rec["mesh"],
            remat=rec.get("remat", "full"), fsdp=rec.get("fsdp", True),
        )
        flops, hbm, wire = a["flops"], a["bytes_accessed"], a["wire_bytes"]
        src = "analytic"
    t_compute = flops / PEAK_FLOPS
    t_memory = hbm / HBM_BW
    t_coll = wire / LINK_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops_cell(rec["arch"], rec["shape"]) / nchips
    useful = mf / flops if flops else 0.0
    bound = max(terms.values())
    # roofline fraction: useful work at peak over the modelled step time
    frac = (mf / PEAK_FLOPS) / bound if bound else 0.0
    hints = {
        "compute": "reduce recompute (remat policy) / increase arithmetic intensity",
        "memory": "fuse + keep working set in VMEM (kernel demotion), cast activations bf16",
        "collective": "reshard to cut all-gathers; overlap collectives with compute",
    }
    return {
        **{k: rec[k] for k in ("arch", "shape", "mesh", "kind")},
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops_per_chip": mf,
        "useful_ratio": useful,
        "roofline_fraction": frac,
        "hint": hints[dominant],
        "temp_gib": rec["memory"]["temp_bytes"] / 2**30,
        "source": src,
    }


def markdown_table(rows: List[Dict[str, Any]], results: List[Dict[str, Any]]) -> str:
    out = [
        "| arch | shape | mesh | compute s | memory s | collective s | dominant "
        "| useful ratio | roofline frac | note |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['t_compute_s']:.3e} | {r['t_memory_s']:.3e} | {r['t_collective_s']:.3e} "
            f"| **{r['dominant']}** | {r['useful_ratio']:.2f} | {r['roofline_fraction']:.2f} "
            f"| {r['hint']} |"
        )
    for rec in results:
        if rec.get("status") == "skipped":
            out.append(
                f"| {rec['arch']} | {rec['shape']} | {rec['mesh']} | — | — | — "
                f"| skipped | — | — | {rec['skip_reason']} |"
            )
    return "\n".join(out)


def roofline_rows(path: str = RESULTS_FILE, mesh: str = "16x16") -> List[str]:
    """CSV rows for benchmarks.run (single-pod table per the assignment)."""
    try:
        results = load_results(path)
    except FileNotFoundError:
        return ["roofline_missing,0.0,run launch/dryrun.py first"]
    rows = []
    for rec in results:
        if rec["mesh"] != mesh or rec.get("mode") != "rolled":
            continue
        a = analyze(rec)
        if a is None:
            reason = rec.get("skip_reason", rec.get("error", ""))[:60]
            rows.append(f"roofline_{rec['arch']}_{rec['shape']},0.0,{rec['status']}:{reason}")
            continue
        dom_us = max(a["t_compute_s"], a["t_memory_s"], a["t_collective_s"]) * 1e6
        rows.append(
            f"roofline_{a['arch']}_{a['shape']},{dom_us:.1f},"
            f"dom={a['dominant']} frac={a['roofline_fraction']:.2f} useful={a['useful_ratio']:.2f}"
        )
    return rows


if __name__ == "__main__":
    results = load_results()
    rows = [a for r in results if (a := analyze(r))]
    print(markdown_table(rows, results))
