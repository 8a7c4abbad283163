"""Roofline analysis over the dry-run artifacts (§Roofline deliverable).

Reads ``benchmarks/results/dryrun/*.json`` (written by repro.launch.dryrun)
and derives, per (arch x shape x mesh):

  compute term    = HLO_FLOPs / (chips * peak)   [per-device flops / peak]
  memory term     = HLO_bytes / (chips * HBM_bw)
  collective term = collective_bytes / (chips * link_bw)

plus MODEL_FLOPS = 6 * N(_active) * tokens (train) or 2 * N_active * tokens
(inference) with an explicit attention/SSM correction, and the useful-compute
ratio MODEL_FLOPS / (HLO_FLOPs * chips).

The peaks are the running device's (``repro.core.hw.chip_for``), or those
of the chip named by ``--device-kind`` (a key of ``repro.core.hw.CHIPS``,
e.g. "TPU v5 lite"); with neither, the roofline is "not measured".

Usage: PYTHONPATH=src python -m benchmarks.roofline [--dir ...] [--md]
       [--device-kind KIND]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from repro.configs import get_config
from repro.configs.base import SHAPES
from repro.core import hw


def model_flops(arch: str, shape_name: str) -> float:
    """Useful (model) FLOPs for one step of this cell, global."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        base = 6.0 * n_active * tokens
        attn = 3.0 * _attn_fwd_flops(cfg, shape.seq_len) * shape.global_batch
        return base + attn
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n_active * tokens + _attn_fwd_flops(
            cfg, shape.seq_len) * shape.global_batch
    # decode: one token per sequence; attention reads the whole cache
    per_tok = 2.0 * n_active + _decode_attn_flops(cfg, shape.seq_len)
    return per_tok * shape.global_batch


def _attn_fwd_flops(cfg, S: int) -> float:
    """Softmax-attention QK^T + PV flops per sequence (causal ~ S^2/2 x2)."""
    total = 0.0
    for s in cfg.stages:
        if s.block in ("dense", "moe"):
            dh = (cfg.nope_head + cfg.rope_head) if s.attn == "mla" else cfg.d_head
            dv = cfg.v_head if s.attn == "mla" else cfg.d_head
            eff = min(S, s.window) if s.window else S
            per_layer = 2 * S * eff * cfg.n_heads * (dh + dv) / (1 if s.window else 2)
            total += s.n_layers * per_layer
        elif s.shared_attn_every:
            n_attn = s.n_layers // s.shared_attn_every
            total += n_attn * 2 * S * S * cfg.n_heads * 2 * cfg.d_head / 2
    return total


def _decode_attn_flops(cfg, S: int) -> float:
    total = 0.0
    for s in cfg.stages:
        if s.block in ("dense", "moe"):
            dh = (cfg.nope_head + cfg.rope_head) if s.attn == "mla" else cfg.d_head
            dv = cfg.v_head if s.attn == "mla" else cfg.d_head
            eff = min(S, s.window) if s.window else S
            total += s.n_layers * 2 * eff * cfg.n_heads * (dh + dv)
        elif s.shared_attn_every:
            n_attn = s.n_layers // s.shared_attn_every
            total += n_attn * 2 * S * cfg.n_heads * 2 * cfg.d_head
    return total


def analyze(rec: dict, chip: hw.ChipSpec) -> dict:
    chips = rec["chips"]
    if "corrected" in rec:
        # loop-corrected HLO walker (XLA's cost_analysis counts while bodies
        # once; see repro.launch.hlo_cost)
        flops_dev = rec["corrected"]["dot_flops_per_device"]
        bytes_dev = rec["corrected"]["dot_bytes_per_device"]
        coll_dev = rec["corrected"]["collective_bytes_per_device"]
    else:
        flops_dev = rec["cost"]["flops_per_device"]
        bytes_dev = rec["cost"]["bytes_per_device"]
        coll_dev = rec["collectives"]["total_bytes"]
    compute_s = flops_dev / chip.peak_flops
    memory_s = bytes_dev / chip.hbm_bw
    collective_s = coll_dev / chip.ici_bw_per_link
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec["arch"], rec["shape"])
    hlo_global = flops_dev * chips
    bound_s = max(terms.values())
    return {
        **{k: round(v, 6) for k, v in terms.items()},
        "dominant": dominant.replace("_s", ""),
        "model_flops": mf,
        "hlo_flops_global": hlo_global,
        "useful_ratio": round(mf / hlo_global, 4) if hlo_global else 0.0,
        "roofline_fraction": round(
            (mf / chips / chip.peak_flops) / bound_s, 4) if bound_s else 0.0,
        "step_lower_bound_s": round(bound_s, 6),
    }


def load(dir_: str, chip: hw.ChipSpec, mesh: str = "16x16") -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(dir_, f"*_{mesh}.json"))):
        with open(path) as f:
            rec = json.load(f)
        if "error" in rec:
            out.append(rec)
            continue
        rec["roofline"] = analyze(rec, chip)
        out.append(rec)
    return out


def as_markdown(recs: list[dict]) -> str:
    hdr = ("| arch | shape | mesh | GiB/dev | fits | compute s | memory s | "
           "collective s | dominant | useful | roofline frac |\n"
           "|---|---|---|---|---|---|---|---|---|---|---|")
    rows = [hdr]
    for r in recs:
        if "error" in r:
            rows.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                        f"ERROR: {r['error'][:60]} | | | | | | | |")
            continue
        rf = r["roofline"]
        mem = r["memory"]["live_bytes_per_device"] / 2**30
        rows.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | {mem:.2f} | "
            f"{'Y' if r['memory']['fits_16GiB'] else 'N'} | "
            f"{rf['compute_s']:.4g} | {rf['memory_s']:.4g} | "
            f"{rf['collective_s']:.4g} | {rf['dominant']} | "
            f"{rf['useful_ratio']:.3f} | {rf['roofline_fraction']:.3f} |")
    return "\n".join(rows)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="benchmarks/results/dryrun")
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--md", action="store_true")
    ap.add_argument("--device-kind", choices=sorted(hw.CHIPS), default=None,
                    help="peaks of this chip instead of the running device's")
    args = ap.parse_args()
    chip = hw.CHIPS[args.device_kind] if args.device_kind else hw.chip_for()
    if chip is None:
        print("roofline: not measured (no accelerator; name the target "
              "chip with --device-kind)")
        return
    recs = load(args.dir, chip, args.mesh)
    if args.md:
        print(as_markdown(recs))
    else:
        for r in recs:
            if "error" in r:
                print(f"{r['arch']},{r['shape']},ERROR")
                continue
            rf = r["roofline"]
            print(f"{r['arch']},{r['shape']},{rf['dominant']},"
                  f"{rf['compute_s']:.5g},{rf['memory_s']:.5g},"
                  f"{rf['collective_s']:.5g},{rf['useful_ratio']},"
                  f"{rf['roofline_fraction']}")


if __name__ == "__main__":
    main()
