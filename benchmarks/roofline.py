"""Roofline analysis from dry-run records (deliverable g).

Reads the JSONL written by repro.launch.dryrun and derives, per
(arch × shape) on the single-pod mesh:

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s        [s]
    memory term     = HLO_traffic_per_device / HBM_bw           [s]
    collective term = collective_bytes_per_device / link_bw     [s]

(the dry-run costs are already per-device — the compiled module is the SPMD
per-device program — so no further division by chip count is needed),
plus MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) per device and the
usefulness ratio MODEL_FLOPS / HLO_FLOPs, and names the dominant term.

Usage:
    PYTHONPATH=src python -m benchmarks.roofline dryrun_results_single.jsonl
"""
from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional

from repro import configs
from repro.data.synthetic import INPUT_SHAPES
from repro.launch.mesh import peaks_for

# repro.launch.dryrun compiles for v5e pod meshes
TARGET = peaks_for("TPU v5 lite")


def count_params(cfg, active_only: bool = False) -> float:
    """Analytic parameter count (embedding + per-layer) for MODEL_FLOPS."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    total = cfg.vocab_size * d  # embeddings (tied)
    if not cfg.tie_embeddings:
        total += cfg.vocab_size * d
    for mixer, ffn in cfg.layer_kinds():
        if mixer in ("global", "local"):
            total += d * hd * (cfg.n_heads * 2 + cfg.n_kv_heads * 2)
            if cfg.n_enc_layers:  # cross attention
                total += d * hd * (cfg.n_heads * 2 + cfg.n_kv_heads * 2)
        else:  # mamba
            di = cfg.ssm.expand * d
            nh = di // cfg.ssm.head_dim
            conv_dim = di + 2 * cfg.ssm.n_groups * cfg.ssm.d_state
            total += d * (2 * di + 2 * cfg.ssm.n_groups * cfg.ssm.d_state + nh)
            total += cfg.ssm.d_conv * conv_dim + di * d
        if ffn == "dense":
            total += 3 * d * cfg.d_ff
        elif ffn == "moe":
            f = cfg.moe_d_ff or cfg.d_ff
            m = cfg.routing.n_experts
            n_eff = cfg.routing.top_k if active_only else m
            total += 3 * d * f * n_eff
            total += d * m  # router
            if cfg.dense_residual:
                total += 3 * d * cfg.d_ff
            if cfg.n_shared_experts:
                total += 3 * d * f * cfg.n_shared_experts
    if cfg.shared_attn_every:
        total += d * hd * (cfg.n_heads * 2 + cfg.n_kv_heads * 2) + 3 * d * cfg.d_ff
    if cfg.n_enc_layers:
        total += cfg.n_enc_layers * (
            d * hd * (cfg.n_heads * 2 + cfg.n_kv_heads * 2) + 3 * d * cfg.d_ff
        )
    return float(total)


def model_flops_per_device(arch: str, shape_name: str, n_chips: int) -> float:
    """6·N·D for training (N = active params, D = tokens); 2·N·D for
    inference steps. Per device = global / n_chips."""
    cfg = configs.get(arch)
    shape = INPUT_SHAPES[shape_name]
    n_active = count_params(cfg, active_only=True)
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        g = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        g = 2.0 * n_active * tokens
    else:  # decode: one token per sequence
        g = 2.0 * n_active * shape.global_batch
    return g / n_chips


def roofline_row(rec: Dict) -> Optional[Dict]:
    if rec.get("status") != "ok":
        return None
    t_compute = rec["flops"] / TARGET.bf16_flops
    t_memory = rec["traffic_bytes"] / TARGET.hbm_bw
    t_coll = rec["collective_bytes"].get("total", 0.0) / TARGET.ici_bw
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops_per_device(rec["arch"], rec["shape"], rec["n_chips"])
    return {
        "arch": rec["arch"],
        "shape": rec["shape"],
        "mesh": rec["mesh"],
        "compute_s": t_compute,
        "memory_s": t_memory,
        "collective_s": t_coll,
        "dominant": dominant,
        "bound_s": terms[dominant],
        "model_flops": mf,
        "useful_ratio": mf / rec["flops"] if rec["flops"] else float("nan"),
        # TPU-adjusted peak: CPU-backend bf16->f32 dot-legalization copies
        # removed (dryrun record 'cpu_upcast_bytes'; methodology in
        # hlo_cost.cpu_bf16_upcast_bytes)
        "peak_gb": (rec.get("peak_bytes_tpu", rec.get("peak_bytes")) or 0) / 2**30,
        "peak_gb_raw": (rec.get("peak_bytes") or 0) / 2**30,
        "fits_16gb": ((rec.get("peak_bytes_tpu", rec.get("peak_bytes")) or 0) / 2**30)
        < 16.0,
    }


def analyze_file(path: str) -> List[Dict]:
    # keep the LAST record per (arch, shape, mesh) — re-runs supersede fails
    latest: Dict = {}
    order: List = []
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            key = (rec["arch"], rec["shape"], rec.get("mesh"))
            if key not in latest:
                order.append(key)
            latest[key] = rec
    rows = []
    for key in order:
        rec = latest[key]
        row = roofline_row(rec)
        if row:
            rows.append(row)
        elif rec.get("status", "").startswith("FAIL"):
            rows.append(
                {"arch": rec["arch"], "shape": rec["shape"],
                 "mesh": rec.get("mesh"), "dominant": "FAILED"}
            )
    return rows


def format_table(rows: List[Dict]) -> str:
    hdr = (
        f"{'arch':<24}{'shape':<13}{'compute_ms':>11}{'memory_ms':>11}"
        f"{'coll_ms':>10}{'dominant':>11}{'useful':>8}{'peakGB':>8}{'fits':>6}"
    )
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        if r["dominant"] == "FAILED":
            lines.append(f"{r['arch']:<24}{r['shape']:<13}{'— FAILED —':>40}")
            continue
        lines.append(
            f"{r['arch']:<24}{r['shape']:<13}"
            f"{r['compute_s']*1e3:>11.2f}{r['memory_s']*1e3:>11.2f}"
            f"{r['collective_s']*1e3:>10.2f}{r['dominant']:>11}"
            f"{r['useful_ratio']:>8.2f}{r['peak_gb']:>8.2f}"
            f"{'y' if r.get('fits_16gb') else 'N':>6}"
        )
    return "\n".join(lines)


def main(argv=None):
    path = (argv or sys.argv[1:])[0] if (argv or sys.argv[1:]) else "dryrun_results_single.jsonl"
    rows = analyze_file(path)
    print(format_table(rows))
    # headline summaries for EXPERIMENTS.md
    ok = [r for r in rows if r["dominant"] != "FAILED"]
    if ok:
        worst = min(ok, key=lambda r: r["useful_ratio"] if r["useful_ratio"] == r["useful_ratio"] else 9)
        coll = max(ok, key=lambda r: r["collective_s"])
        print(f"\nworst useful-ratio: {worst['arch']} x {worst['shape']} ({worst['useful_ratio']:.3f})")
        print(f"most collective-bound: {coll['arch']} x {coll['shape']} ({coll['collective_s']*1e3:.1f} ms)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
