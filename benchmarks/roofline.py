"""Roofline terms per (arch x shape x mesh) from the dry-run artifacts.

  compute    = HLO_FLOPs / peak bf16 FLOP/s
  memory     = HLO_bytes / peak HBM bytes/s
  collective = ICI_bytes/chip / ICI link bytes/s + DCN_bytes/chip / DCN bytes/s

with the peaks of the record's `device_kind` from `PEAKS`.

HLO_FLOPs / HLO_bytes are the loop-aware totals from repro.analysis.hlo
(XLA's cost_analysis visits while bodies once; we verified the raw numbers
undercount by the scan trip count and report both). Collective bytes use a
ring model per op with group size parsed from replica_groups; groups of
size == n_pods are attributed to DCN.

MODEL_FLOPS uses 6*N*D (dense) / 6*N_active*D (MoE) with D = tokens
processed by the cell; the ratio MODEL_FLOPS/HLO_FLOPs exposes
remat/dispatch waste. All terms are per-step seconds; the dominant term is
the bottleneck and its ratio to the compute term is the roofline fraction.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Optional

from repro.configs.base import ALL_SHAPES
from repro.configs.registry import get_config

# Per-chip peaks, keyed by `jax.Device.device_kind`. A kind missing here is
# an error, never a default. Source for "TPU v5 lite" (TPU v5e): Google
# Cloud documentation, "TPU v5e" -- 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB
# of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect (4 links of
# 50 GB/s). The DCN figure per host pair is a planning assumption, not a
# published peak.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9,
                    "ici_bw": 50e9, "dcn_bw": 6.25e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table row of one device kind; raises for unknown kinds."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak figures for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


DRYRUN = Path(__file__).resolve().parent / "dryrun_results"
RESULTS = Path(__file__).resolve().parent / "results"


def active_params(arch: str) -> float:
    """Active parameters per token (MoE: shared + top_k experts only)."""
    cfg = get_config(arch)
    from repro.models.model import padded_vocab
    d = cfg.d_model
    # embeddings + head
    n = padded_vocab(cfg) * d * (1 if cfg.tie_embeddings else 2)
    per_layer = {}
    for i in range(cfg.num_layers):
        kind = cfg.pattern[(i - cfg.first_k_dense) % len(cfg.pattern)] \
            if i >= cfg.first_k_dense else cfg.pattern[0]
        ffn = cfg.ffn_pattern[(i - cfg.first_k_dense) % len(cfg.pattern)] \
            if i >= cfg.first_k_dense else "dense"
        p = 0.0
        hd, h, kv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
        if kind in ("attn", "swa"):
            p += d * h * hd + 2 * d * kv * hd + h * hd * d
        elif kind == "mla":
            m = cfg.mla
            qk = m.nope_head_dim + m.rope_head_dim
            p += (d * m.q_lora_rank + m.q_lora_rank * h * qk
                  + d * m.kv_lora_rank + d * m.rope_head_dim
                  + m.kv_lora_rank * h * (m.nope_head_dim + m.v_head_dim)
                  + h * m.v_head_dim * d)
        elif kind == "mamba":
            di = cfg.mamba.expand * d
            dtr = max(1, d // 16)
            p += d * 2 * di + di * (dtr + 2 * cfg.mamba.d_state) \
                + dtr * di + 2 * di * d
        elif kind in ("mlstm", "slstm"):
            di = int(2.0 * d)
            p += d * 2 * di + 3 * di * di + di * d if kind == "mlstm" \
                else d * 4 * d + 2 * d * 2 * d
        if ffn == "dense" or i < cfg.first_k_dense:
            w = cfg.d_ff if cfg.moe is None else 2 * d
            w = w or 4 * d
            p += 3 * d * w
        elif ffn == "moe":
            mc = cfg.moe
            p += 3 * d * mc.d_ff_expert * (mc.top_k + mc.num_shared_experts)
            p += d * mc.num_experts  # router
        per_layer[i] = p
    return n + sum(per_layer.values())


def mixer_flops(arch: str, shape) -> float:
    """Forward FLOPs of the sequence mixers (not counted by 6*N*D): the
    quadratic/windowed attention term dominates long-context cells."""
    cfg = get_config(arch)
    b, s = shape.global_batch, shape.seq_len
    total = 0.0
    for i in range(cfg.num_layers + cfg.encoder_layers):
        if i < cfg.num_layers:
            kind = (cfg.pattern[0] if i < cfg.first_k_dense else
                    cfg.pattern[(i - cfg.first_k_dense) % len(cfg.pattern)])
        else:
            kind = "attn"  # encoder layers
        h, hd = cfg.num_heads, cfg.head_dim
        if kind in ("attn", "swa", "mla"):
            if kind == "mla":
                m = cfg.mla
                dd = m.nope_head_dim + m.rope_head_dim + m.v_head_dim
            else:
                dd = 2 * hd
            if shape.kind == "decode":
                kv = s if kind != "swa" else min(s, cfg.window_size)
                total += 2.0 * b * h * kv * dd
            else:
                kv_eff = s / 2 if kind != "swa" else \
                    min(cfg.window_size, s / 2)
                total += 2.0 * b * h * s * kv_eff * dd
        elif kind == "mamba":
            di = cfg.mamba.expand * cfg.d_model
            per_tok = 9.0 * di * cfg.mamba.d_state
            total += per_tok * (b if shape.kind == "decode" else b * s)
        elif kind == "mlstm":
            di = int(2.0 * cfg.d_model)
            hd_m = di // cfg.num_heads
            chunk = 256
            if shape.kind == "decode":
                total += 4.0 * b * di * hd_m
            else:
                total += 2.0 * b * cfg.num_heads * s * chunk * (2 * hd_m)
        elif kind == "slstm":
            total += 8.0 * (cfg.d_model // cfg.xlstm.num_heads_slstm) \
                * cfg.d_model * (b if shape.kind == "decode" else b * s)
    return total


def model_flops(arch: str, shape_name: str) -> float:
    shape = next(s for s in ALL_SHAPES if s.name == shape_name)
    n_active = active_params(arch)
    mx = mixer_flops(arch, shape)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens + 3.0 * mx
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens + mx
    return 2.0 * n_active * shape.global_batch + mx  # decode: 1 tok/lane


def load_cell(arch: str, shape: str, mesh: str, tag: str = "baseline"
              ) -> Optional[dict]:
    f = DRYRUN / f"{arch}__{shape}__{mesh}__{tag}.json"
    if not f.exists():
        return None
    return json.loads(f.read_text())


def roofline_terms(rec: dict) -> dict:
    """All analyzer quantities (hlo_flops/hlo_bytes/collective bytes) are
    PER-DEVICE — the analyzed HLO is the SPMD single-device program — so
    terms divide by per-chip peaks only. MODEL_FLOPS is global and divides
    by the chip count."""
    chips = rec["devices"]
    pk = peaks(rec["device_kind"])
    compute_s = rec["hlo_flops"] / pk["bf16_flops"]
    # memory term uses the kernel-adjusted traffic (innermost loop bodies =
    # one fused Pallas kernel); the raw post-CPU-fusion number is reported
    # alongside as memory_s_xla
    memory_s = (rec.get("hlo_bytes_kernel_adj", rec["hlo_bytes"])
                / pk["hbm_bw"])
    memory_s_xla = rec["hlo_bytes"] / pk["hbm_bw"]
    ici_bytes = (rec["collective_bytes_total"]
                 - rec.get("collective_bytes_dcn", 0.0))
    coll_s = ici_bytes / pk["ici_bw"] \
        + rec.get("collective_bytes_dcn", 0.0) / pk["dcn_bw"]
    mf = model_flops(rec["arch"], rec["shape"])
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "memory_s_xla": memory_s_xla,
             "collective_s": coll_s,
             "model_flops": mf,
             "useful_flops_ratio": mf / max(chips * rec["hlo_flops"], 1.0)}
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: terms[k])
    terms["bottleneck"] = dom.replace("_s", "")
    step = max(compute_s, memory_s, coll_s)
    terms["roofline_fraction"] = (mf / (chips * pk["bf16_flops"])) / step \
        if step > 0 else 0.0
    return terms


def table(mesh: str = "single", tag: str = "baseline") -> list:
    from repro.configs.base import shapes_for
    from repro.configs.registry import ARCH_IDS
    rows = []
    for arch in ARCH_IDS:
        for sh in shapes_for(get_config(arch)):
            rec = load_cell(arch, sh.name, mesh, tag)
            if rec is None or not rec.get("ok"):
                rows.append({"arch": arch, "shape": sh.name, "mesh": mesh,
                             "ok": False})
                continue
            t = roofline_terms(rec)
            rows.append({"arch": arch, "shape": sh.name, "mesh": mesh,
                         "ok": True, **t,
                         "hbm_gb": rec.get("hbm_per_dev_gb_tpu_est"),
                         "fits": rec.get("fits_16gb")})
    return rows


def run() -> dict:
    out = {"single": table("single"), "multi": table("multi")}
    RESULTS.mkdir(exist_ok=True)
    (RESULTS / "roofline.json").write_text(json.dumps(out, indent=1))
    return out


def rows():
    out = run()
    for r in out["single"]:
        if not r.get("ok"):
            yield (f"roofline.{r['arch']}.{r['shape']}", -1, "MISSING")
            continue
        yield (f"roofline.{r['arch']}.{r['shape']}",
               r["roofline_fraction"],
               f"bottleneck={r['bottleneck']} "
               f"useful={r['useful_flops_ratio']:.2f} "
               f"hbm={r['hbm_gb']}GB")
