"""Roofline analysis (deliverable g).

Reads the dry-run JSONs (experiments/dryrun/*.json) and reports, per
(arch × shape × mesh):

    compute term    = FLOPs / (chips × 197e12)          [bf16 peak]
    memory term     = bytes / (chips × 819e9)           [HBM BW]
    collective term = collective bytes / 50e9           [per-link ICI]

FLOPs/bytes sources, in order of trust:
  1. scan-corrected HLO cost: cost(1-period model) + (P−1)·Δ where
     Δ = cost(2p) − cost(1p) — corrects XLA's while-body single-count
     (recorded by dryrun --calibrate; residual undercount remains for
     recurrent *prefill* paths whose inner sequence scans are also
     while-loops: xlstm prefill, mamba prefill — flagged).
  2. analytic closed-form model (this module) — complete for all paths.

MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE) per the assignment; the
ratio MODEL_FLOPS / HLO_FLOPS exposes remat/redundant compute.
"""
from __future__ import annotations

import glob
import json
import os

from repro import configs
from repro.configs.base import INPUT_SHAPES, for_shape
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

DRYRUN_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "experiments", "dryrun")


# ----------------------------------------------------------------------
# Analytic cost model
# ----------------------------------------------------------------------
def _layer_matmul_params(cfg, block, ffn, active=True):
    """Matmul params of one layer — reuses the config's param formulas."""
    import dataclasses
    one = dataclasses.replace(
        cfg, n_layers=1, n_prefix_layers=0, block_pattern=(block,),
        ffn_pattern=(ffn,), n_encoder_layers=0)
    base = dataclasses.replace(one, n_layers=0, block_pattern=(block,),
                               ffn_pattern=(ffn,))
    return one.param_count(active_only=active) - base.param_count()


def analytic_flops(arch: str, shape_name: str, remat: bool = True) -> dict:
    """Global FLOPs for one step of (arch, shape).  Returns a breakdown."""
    shape = INPUT_SHAPES[shape_name]
    cfg = for_shape(configs.get_config(arch), shape)
    B = shape.batch
    S = shape.seq if shape.kind != "decode" else 1
    ctx = shape.seq                                  # decode context length
    T = B * S
    d, nq, nkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    proj = 0.0       # 2·T·params for every matmul layer
    seqmix = 0.0     # attention scores / ssm state math

    def attn_extra(n_layers):
        if cfg.is_mla and shape.kind == "decode":
            # absorbed: scores in latent space + rope part + absorb einsums
            r = cfg.kv_lora_rank
            return n_layers * B * (2 * nq * ctx * (r + cfg.rope_head_dim)
                                   + 2 * nq * ctx * r
                                   + 4 * nq * hd * r)
        qk_dim = hd + (cfg.rope_head_dim if cfg.is_mla else 0)
        if shape.kind == "decode":
            skv = min(ctx, cfg.sliding_window) if cfg.attention == \
                "sliding" else ctx
            return n_layers * 4 * B * skv * nq * qk_dim
        skv = S / 2 if cfg.attention == "full" else min(cfg.sliding_window,
                                                        S / 2)
        return n_layers * 4 * B * S * skv * nq * qk_dim

    n_attn = sum(b == "attn" for b in cfg.block_pattern) * cfg.n_periods \
        + cfg.n_prefix_layers
    n_mamba = sum(b == "mamba" for b in cfg.block_pattern) * cfg.n_periods
    n_mlstm = sum(b == "mlstm" for b in cfg.block_pattern) * cfg.n_periods
    n_slstm = sum(b == "slstm" for b in cfg.block_pattern) * cfg.n_periods

    # projections: 2 flops per param per token (active params for MoE)
    nonembed = cfg.param_count(active_only=True) - cfg.vocab * d * \
        (1 if cfg.tie_embeddings else 2)
    proj = 2.0 * T * nonembed
    # MoE capacity padding overhead
    if cfg.n_experts:
        moe_layers = sum(f == "moe" for f in cfg.ffn_pattern) * \
            cfg.n_periods
        expert_p = 3 * d * cfg.d_expert * cfg.moe_top_k
        proj += 2.0 * T * moe_layers * expert_p * (cfg.capacity_factor - 1)

    seqmix += attn_extra(n_attn)
    di, ds = cfg.d_inner, cfg.mamba_d_state
    seqmix += n_mamba * T * (10.0 * di * ds + 2 * cfg.mamba_d_conv * di)
    dim = int(cfg.mlstm_proj_factor * d)
    dhm = dim // max(nq, 1)
    if shape.kind == "train":
        seqmix += n_mlstm * 4.0 * B * S * S * dim        # parallel form
    else:
        seqmix += n_mlstm * T * 5.0 * dim * dhm          # recurrent form
    # slstm recurrent matmuls are in the param count; elementwise ~ free

    # lm head + encoder (already in param_count via encoder formulas)
    total_fwd = proj + seqmix
    mult = 1.0
    if shape.kind == "train":
        mult = 3.0 + (1.0 if remat else 0.0)             # fwd + bwd (+remat)
    return {"fwd_proj": proj, "fwd_seqmix": seqmix,
            "total": total_fwd * mult, "multiplier": mult,
            "model_flops": 6.0 * nonembed * T,
            "model_flops_mode": (6.0 if shape.kind == "train" else 2.0)
            * nonembed * T}


def analytic_hbm_bytes(arch: str, shape_name: str) -> float:
    """Rough global HBM traffic for one step (documented estimate)."""
    shape = INPUT_SHAPES[shape_name]
    cfg = for_shape(configs.get_config(arch), shape)
    B, S = shape.batch, shape.seq
    P_total = cfg.param_count()
    d = cfg.d_model
    L = cfg.n_layers
    if shape.kind == "decode":
        w = 2.0 * P_total                       # every weight read (bf16)
        cache = _cache_bytes(cfg, B, S)
        return w + 2.0 * cache                  # read + (re)write
    acts = L * B * S * d * 16.0                 # per-layer act traffic, bf16
    w = 2.0 * P_total
    if shape.kind == "train":
        return 3.0 * acts + 12.0 * P_total * 4  # grads + adam m,v rw (fp32)
    cache = _cache_bytes(cfg, B, S)
    return acts + w + cache


def _cache_bytes(cfg, B, S):
    Sc = min(S, cfg.sliding_window) if cfg.attention == "sliding" else S
    n_attn = sum(b == "attn" for b in cfg.block_pattern) * cfg.n_periods \
        + cfg.n_prefix_layers
    if cfg.is_mla:
        per = Sc * (cfg.kv_lora_rank + cfg.rope_head_dim) * 2.0
    else:
        per = Sc * 2 * cfg.n_kv_heads * cfg.head_dim * 2.0
    ssm_layers = sum(b in ("mamba", "mlstm", "slstm")
                     for b in cfg.block_pattern) * cfg.n_periods
    ssm = ssm_layers * cfg.d_inner * cfg.mamba_d_state * 4.0
    return B * (n_attn * per + ssm)


# ----------------------------------------------------------------------
# Report builder
# ----------------------------------------------------------------------
def _recurrent_prefill(cfg, kind):
    """True when per-layer cost still hides a long sequential scan even in
    calibration (mLSTM/sLSTM recurrence over S) — analytic is primary."""
    rec_blocks = {"mlstm", "slstm"}
    has = any(b in rec_blocks for b in cfg.block_pattern)
    if not has:
        return False
    if kind == "decode":
        return False                      # trip-1 scans: exact
    if kind == "train":
        # mLSTM trains in the parallel form; only sLSTM scans over S
        return "slstm" in cfg.block_pattern
    return True                           # prefill: recurrent over S


def corrected_hlo(rec):
    """Scan-corrected PER-DEVICE HLO flops/bytes:
    c0 (0 body periods) + n_units * (c1 - c0), with inner scans collapsed
    to trip-1 during calibration (exact single-count).  Multiplied by
    n_chips for the global figure."""
    cal = rec.get("scan_calibration")
    if not cal or "cost_0p" not in cal or "cost_1p" not in cal:
        return None
    c0, c1 = cal["cost_0p"], cal["cost_1p"]
    n = cal["n_units"]
    chips = rec.get("n_chips", 256)
    out = {}
    for key in ("flops", "bytes accessed"):
        if key in c0 and key in c1:
            out[key] = chips * (c0[key] + n * (c1[key] - c0[key]))
    return out or None


def load_records(dryrun_dir=DRYRUN_DIR):
    recs = []
    for f in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        try:
            recs.append(json.load(open(f)))
        except Exception:
            pass
    return recs


def roofline_row(rec):
    if rec.get("status") != "ok":
        return None
    arch, shape, mesh = rec["arch"], rec["shape"], rec["mesh"]
    chips = rec.get("n_chips", 256)
    af = analytic_flops(arch, shape)
    ab = analytic_hbm_bytes(arch, shape)
    ch = corrected_hlo(rec)
    cfg = for_shape(configs.get_config(arch), INPUT_SHAPES[shape])
    prefer_analytic = _recurrent_prefill(cfg, rec["kind"]) or not ch
    # compute term: corrected HLO (reflects what XLA actually compiled,
    # including replicated/rematerialised compute) unless a recurrent
    # prefill hides a sequence scan; memory term: ALWAYS analytic (HLO
    # "bytes accessed" counts unfused intermediates and the calibration
    # unroll, not HBM traffic).
    flops = af["total"] if prefer_analytic or not ch.get("flops") \
        else ch["flops"]
    hbytes = ab
    hlo_bytes = ch.get("bytes accessed") if ch else None
    coll = rec["collectives"]["total_collective_bytes"]
    t_comp = flops / (chips * PEAK_FLOPS_BF16)
    t_mem = hbytes / (chips * HBM_BW)
    t_coll = coll / ICI_BW          # HLO shapes are already per-device
    dom = max((t_comp, "compute"), (t_mem, "memory"),
              (t_coll, "collective"))[1]
    return {
        "arch": arch, "shape": shape, "mesh": mesh, "chips": chips,
        "flops": flops, "analytic_flops": af["total"],
        "model_flops": af["model_flops"],
        "hbm_bytes": hbytes, "analytic_bytes": ab,
        "hlo_bytes_diag": hlo_bytes,
        "collective_bytes": coll,
        "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": t_coll,
        "bottleneck": dom,
        "useful_ratio": af["model_flops_mode"] / max(flops, 1.0),
        "model_flops_6nd": af["model_flops"],
        "peak_gib_per_chip": rec["memory"]["peak_per_device"] / 2 ** 30,
        "flops_source": "analytic" if prefer_analytic or not ch
        else "hlo-corrected",
    }


def build_table(dryrun_dir=DRYRUN_DIR, mesh="pod16x16"):
    rows = []
    for rec in load_records(dryrun_dir):
        if rec.get("mesh") != mesh:
            continue
        row = roofline_row(rec)
        if row:
            rows.append(row)
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    return rows


def _lever(r):
    """One sentence: what would move the dominant term down."""
    b, shape, arch = r["bottleneck"], r["shape"], r["arch"]
    moe = "moe" in arch or "jamba" in arch or "deepseek-v2" in arch
    if b == "collective":
        if shape == "train_4k":
            return ("overlap grad all-reduce with bwd; reduce-scatter "
                    "grads (ZeRO-2) instead of all-reduce")
        if moe:
            return "all-to-all expert routing instead of gather+psum"
        return ("async collective overlap; duplicate small KV heads "
                "instead of resharding")
    if b == "memory":
        if shape in ("decode_32k", "long_500k"):
            return "int8/paged KV cache; fuse decode attention (flash)"
        return "bf16 master weights or ZeRO-3; CE in vocab chunks"
    if r["useful_ratio"] < 0.5:
        return ("cut non-6ND compute: MoE capacity factor, remat policy, "
                "attention score share")
    return "larger per-chip tiles; batch growth until memory-bound"


def markdown_table(rows):
    hdr = ("| arch | shape | t_comp (ms) | t_mem (ms) | t_coll (ms) | "
           "bottleneck | 6ND/HLO | GiB/chip | src | lever |")
    sep = "|" + "---|" * 10
    out = [hdr, sep]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']*1e3:.2f} | "
            f"{r['t_memory_s']*1e3:.2f} | {r['t_collective_s']*1e3:.2f} | "
            f"**{r['bottleneck']}** | {r['useful_ratio']:.2f} | "
            f"{r['peak_gib_per_chip']:.1f} | {r['flops_source']} | "
            f"{_lever(r)} |")
    return "\n".join(out)


def main():
    rows = build_table()
    print(markdown_table(rows))
    print()
    n_ok = len(rows)
    print(f"{n_ok} combos analysed (single-pod). Bottleneck counts:",
          {b: sum(r['bottleneck'] == b for r in rows)
           for b in ("compute", "memory", "collective")})


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
