"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Three terms per (arch x shape) cell, all per-device seconds on TPU v5e:

  compute    = HLO_FLOPs / peak_FLOPs            (197 TFLOP/s bf16)
  memory     = HLO_bytes / HBM_bw                (819 GB/s)
  collective = ring-weighted collective bytes / ICI link bw (50 GB/s)

HLO_FLOPs/bytes come from the trip-count-aware HLO parse (hlo_analysis);
``xla.cost_analysis`` is recorded alongside but under-counts scan bodies.
MODEL_FLOPS uses the 6ND / 2ND convention (active params for MoE), so the
useful-fraction column exposes remat/padding/causal-waste overheads.
"""
from __future__ import annotations

import glob
import json
import os
from dataclasses import dataclass
from typing import Dict, List, Optional

from repro.configs.base import SHAPES, get_config
from repro.launch.hlo_analysis import collective_link_bytes
from repro.launch.mesh import V5E, hardware


def model_flops(arch: str, shape_name: str) -> float:
    """Global useful flops per step: 6ND train / 2ND inference (+ attention
    term for quadratic-attention archs at long S)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    from repro.models.model import count_params
    n_total = count_params(cfg, include_embed=True,
                           active_only=bool(cfg.num_experts))
    n = n_total - cfg.vocab_size * cfg.d_model   # embedding gather ~free
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        tokens = B * (S // cfg.encdec_tgt_ratio if cfg.is_encdec else S)
        base = 6.0 * n * tokens
        # causal attention fwd+bwd ~ 3 x fwd; fwd = 4*B*S^2/2*H*D per layer
        attn = _attn_flops(cfg, B, S) * 3.0
    elif shape.kind == "prefill":
        tokens = B * S
        base = 2.0 * n * tokens
        attn = _attn_flops(cfg, B, S)
    else:  # decode: 1 token per sequence against an S-long cache
        base = 2.0 * n * B
        attn = _decode_attn_flops(cfg, B, S)
    return base + attn


def _layers_of(cfg, kind):
    n = 0
    for g in cfg.groups:
        for ls in g.layers:
            if ls.mixer == kind:
                n += g.repeat
            if ls.shared_attn and kind == "attn":
                n += g.repeat
    return n


def _attn_flops(cfg, B, S):
    if cfg.num_heads == 0:
        return 0.0
    hd = cfg.num_heads * cfg.head_dim
    full = _layers_of(cfg, "attn")
    local = _layers_of(cfg, "attn_local")
    w = min(cfg.window_size, S)
    f = 4.0 * B * (S * S / 2) * hd * full
    f += 4.0 * B * (S * w - w * w / 2) * hd * local
    return f


def _decode_attn_flops(cfg, B, S):
    if cfg.num_heads == 0:
        return 0.0
    hd = cfg.num_heads * cfg.head_dim
    full = _layers_of(cfg, "attn")
    local = _layers_of(cfg, "attn_local")
    return 4.0 * B * (S * full + min(cfg.window_size, S) * local) * hd


@dataclass
class CellRoofline:
    arch: str
    shape: str
    mesh: str
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops_dev: float
    hlo_flops_dev: float
    useful_ratio: float
    roofline_fraction: float
    note: str


_NOTES = {
    "compute": ("compute-bound: cut remat recompute / causal-brick padding, "
                "or raise arithmetic intensity with larger per-chip tiles"),
    "memory": ("HBM-bound: fuse elementwise chains, keep activations bf16, "
               "shrink remat working set"),
    "collective": ("collective-bound: replace all-reduce with "
                   "reduce-scatter+all-gather (TP-SP), overlap FSDP gathers "
                   "with compute, compress cross-pod grads"),
}


def analyze_cell(rec: dict) -> Optional[CellRoofline]:
    if rec.get("status") != "ok":
        return None
    hlo = rec["hlo"]
    n_dev = hlo.get("n_devices", 256)
    hw = hardware(V5E)       # the dry-run cells model the v5e pod
    peak = hw["peak_flops_bf16"]
    hbm = hw["hbm_bandwidth"]
    link = hw["ici_link_bandwidth"]
    compute_s = hlo["flops"] / peak
    memory_s = hlo["bytes_accessed"] / hbm
    link_bytes = collective_link_bytes(hlo.get("coll_ops", []))
    collective_s = link_bytes / link
    dominant = max(("compute", compute_s), ("memory", memory_s),
                   ("collective", collective_s), key=lambda kv: kv[1])[0]
    mf = model_flops(rec["arch"], rec["shape"]) / n_dev
    useful = mf / max(hlo["flops"], 1.0)
    frac = (mf / peak) / max(compute_s, memory_s, collective_s, 1e-12)
    return CellRoofline(
        arch=rec["arch"], shape=rec["shape"], mesh=rec["mesh"],
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, model_flops_dev=mf, hlo_flops_dev=hlo["flops"],
        useful_ratio=useful, roofline_fraction=frac, note=_NOTES[dominant])


def load_cells(art_dir: str = "artifacts/dryrun", mesh: str = "16x16"
               ) -> List[CellRoofline]:
    out = []
    for fn in sorted(glob.glob(os.path.join(art_dir, "*.json"))):
        with open(fn) as f:
            rec = json.load(f)
        if rec.get("mesh") != mesh:
            continue
        if rec.get("variant", "baseline") != "baseline":
            continue   # §Perf variants live in their own section
        cell = analyze_cell(rec)
        if cell:
            out.append(cell)
    return out


def markdown_table(cells: List[CellRoofline]) -> str:
    hdr = ("| arch | shape | compute s | memory s | collective s | bound | "
           "model/HLO flops | roofline frac |\n"
           "|---|---|---|---|---|---|---|---|")
    rows = [hdr]
    for c in sorted(cells, key=lambda c: (c.arch, c.shape)):
        rows.append(
            f"| {c.arch} | {c.shape} | {c.compute_s:.3f} | {c.memory_s:.3f} "
            f"| {c.collective_s:.3f} | {c.dominant} | {c.useful_ratio:.2f} "
            f"| {c.roofline_fraction:.3f} |")
    return "\n".join(rows)


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--art", default="artifacts/dryrun")
    ap.add_argument("--mesh", default="16x16")
    args = ap.parse_args()
    cells = load_cells(args.art, args.mesh)
    print(markdown_table(cells))
    worst = sorted(cells, key=lambda c: c.roofline_fraction)[:3]
    collb = [c for c in cells if c.dominant == "collective"]
    print("\nworst roofline fractions:",
          [(c.arch, c.shape, round(c.roofline_fraction, 3)) for c in worst])
    print("collective-bound cells:",
          [(c.arch, c.shape) for c in collb][:8])


if __name__ == "__main__":
    main()
