"""Roofline analysis from dry-run JSON artifacts (EXPERIMENTS.md §Roofline).

Terms (per device, against the TPU v5e deployment target, ``TARGET``):
    compute    = HLO_FLOPs_per_device / 197e12          (bf16 MXU peak)
    memory     = HLO_bytes_per_device / 819e9           (HBM bandwidth)
    collective = collective_bytes_per_device / 50e9     (one ICI link, conservative)

plus MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) per training step
(3x forward 2ND for fwd+bwd), and the usefulness ratio
MODEL_FLOPS / (HLO_FLOPs_per_device * chips), which exposes remat/dispatch
waste. For inference kinds the model term is 2*N*D_tokens (no backward).

    PYTHONPATH=src python -m repro.launch.roofline --in-dir experiments/dryrun
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from repro.configs import get_arch, get_shape

#: Published per-chip peaks keyed by ``jax.Device.device_kind``.  Source:
#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s, 1,600 Gbit/s of ICI per chip over four links (50 GB/s is one
#: link, the conservative per-hop figure).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
#: The chip the dry-run analyses (production meshes of v5e pods) target.
TARGET = PEAKS["TPU v5 lite"]


def device_peaks() -> dict | None:
    """Peaks of the device this process runs on, or None when its
    ``device_kind`` is not in :data:`PEAKS`: no roofline share is reported
    for a device whose peaks are unknown."""
    import jax

    return PEAKS.get(jax.devices()[0].device_kind)


# ------------------------------------------------- per-kernel cost model ----
# Analytic FLOPs / bytes-moved per kernel-family dispatch, shared between the
# whole-step analysis below and the per-dispatch profiler (repro.obs.prof),
# so "profiler bytes" and "roofline bytes" cannot drift apart — one formula,
# two consumers.  Bytes are the *mandatory* HBM traffic of the fused op:
# each operand read once at its storage width, the output written once.
# Pure functions of shapes + per-element byte widths: callers (obs.prof)
# extract those from the live arrays / pcsr operand slots.

def gemm_cost(m: float, k: float, n: float, *, a_bytes: float, b_bytes: float,
              out_bytes: float, bias: bool = False,
              residual: bool = False) -> dict:
    """(M,K) x (K,N) fused posit GEMM: decode + dot + epilogue, one launch."""
    byts = m * k * a_bytes + k * n * b_bytes + m * n * out_bytes
    if bias:
        byts += 4.0 * n              # f32 bias vector read
    if residual:
        byts += 4.0 * m * n          # f32 residual read fused into epilogue
    return {"flops": 2.0 * m * k * n, "bytes": float(byts)}


def attention_decode_cost(b: float, hq: float, hkv: float, s: float,
                          d: float, *, kv_bytes: float, q_bytes: float = 4.0,
                          out_bytes: float = 4.0) -> dict:
    """One flash-decode step over a (B,Hkv,S,d) posit-coded KV cache.

    ``s`` is the *allocated* cache length: the analytic bound charges the
    full slot grid (the ragged early-exit only helps past the longest live
    row, which the profiler cannot see from shapes alone)."""
    flops = 4.0 * b * hq * s * d     # q@k^T and p@v, 2 FLOPs/MAC each
    byts = (b * hq * d * (q_bytes + out_bytes)    # q read + out write
            + 2.0 * b * hkv * s * d * kv_bytes)   # K and V code streams
    return {"flops": float(flops), "bytes": float(byts)}


def codec_cost(n: float, *, code_bytes: float, value_bytes: float = 4.0) -> dict:
    """Streaming encode/decode of ``n`` elements (LUT gather / bit pipeline):
    pure memory movement — codes on one side, float values on the other."""
    return {"flops": float(n), "bytes": float(n * (code_bytes + value_bytes))}


def softmax_cost(rows: float, cols: float, *, code_bytes: float) -> dict:
    """Posit-domain softmax over (rows, cols) codes: codes in, codes out;
    ~5 vector ops per element (max, sub, exp, sum, div)."""
    n = rows * cols
    return {"flops": 5.0 * n, "bytes": 2.0 * n * code_bytes}


def bound_times(flops: float, byts: float, coll_bytes: float = 0.0,
                peaks: dict = TARGET) -> dict:
    """Roofline time terms for one dispatch (or one whole step) on a chip
    with ``peaks`` (an entry of :data:`PEAKS`), plus which term binds."""
    terms = {"compute": flops / peaks["flops"], "memory": byts / peaks["hbm_bw"],
             "collective": coll_bytes / peaks["ici_bw"]}
    dominant = max(terms, key=terms.get)
    return {"t_compute_s": terms["compute"], "t_memory_s": terms["memory"],
            "t_collective_s": terms["collective"], "dominant": dominant,
            "bound_s": terms[dominant]}


def param_count(cfg) -> tuple[float, float]:
    """(total, active) parameter counts, embedding included once."""
    d, L, V = cfg.d_model, cfg.n_layers, cfg.vocab
    hd, H, Hkv = cfg.hd, cfg.n_heads, cfg.n_kv
    att = d * (H * hd) + 2 * d * (Hkv * hd) + (H * hd) * d
    if cfg.family == "moe":
        per_expert = 3 * d * cfg.d_ff
        mlp_total = cfg.n_experts * per_expert + d * cfg.n_experts
        mlp_active = cfg.top_k * per_expert + d * cfg.n_experts
        block_t, block_a = att + mlp_total, att + mlp_active
        total = L * block_t + V * d * (1 if cfg.tie_embeddings else 2)
        active = L * block_a + V * d * (1 if cfg.tie_embeddings else 2)
        return float(total), float(active)
    if cfg.family == "zamba":
        di = 2 * d
        ssm = d * (2 * di + 2 * cfg.ssm_state + di // cfg.ssm_head_dim) + di * d
        shared = att + 3 * d * cfg.d_ff
        n_shared = max(1, cfg.n_layers // max(cfg.shared_attn_every, 1))
        total = L * ssm + shared + V * d * 2
        # shared block runs n_shared times: count FLOPs-active accordingly
        active = L * ssm + n_shared * shared + V * d * 2
        return float(total), float(active)
    if cfg.family == "xlstm":
        di = int(d * 2.0)
        mlstm = d * 2 * di + 3 * di * di + 2 * di * cfg.n_heads + di * d
        slstm = d * 4 * d + d * d // cfg.n_heads * 4 + 2 * d * int(d * 4 / 3)
        n_s = sum(1 for i in range(L) if cfg.slstm_every and i % cfg.slstm_every == 1)
        total = (L - n_s) * mlstm + n_s * slstm + V * d * 2
        return float(total), float(total)
    if cfg.family == "whisper":
        enc = cfg.enc_layers * (att + 2 * d * cfg.d_ff)
        dec = L * (2 * att + 2 * d * cfg.d_ff)
        total = enc + dec + V * d
        return float(total), float(total)
    mlp = 3 * d * cfg.d_ff
    total = L * (att + mlp) + V * d * (1 if cfg.tie_embeddings else 2)
    return float(total), float(total)


def model_flops(cfg, shape) -> float:
    """6*N_active*tokens for train, 2*N_active*tokens for inference."""
    _, active = param_count(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * active * tokens
    # decode: one token per sequence
    return 2.0 * active * shape.global_batch


def analyse(rec: dict, probe: dict | None = None) -> dict:
    """probe: matching scan-aware cost probe (launch.costprobe) — preferred
    over the raw compiled numbers, which count while-loop bodies once."""
    if rec.get("skipped") or rec.get("error"):
        return rec
    cfg = get_arch(rec["arch"])
    shape = get_shape(rec["shape"])
    chips = rec["n_chips"]
    if probe and not probe.get("error"):
        fl = probe["flops_per_device"]
        by = probe["bytes_per_device"]
        coll = probe["coll_per_device"]
    else:
        fl = rec["flops_per_device"]
        by = rec["bytes_per_device"]
        coll = sum(v["bytes"] for v in rec.get("collectives", {}).values())

    bt = bound_times(fl, by, coll)
    t_compute, t_memory, t_coll = (bt["t_compute_s"], bt["t_memory_s"],
                                   bt["t_collective_s"])
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = bt["dominant"]
    mf = model_flops(cfg, shape)
    hlo_global = fl * chips
    out = dict(rec)
    out.pop("collectives", None)
    out.update({
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops": mf,
        "useful_ratio": (mf / hlo_global) if hlo_global else 0.0,
        "collective_bytes": coll,
        "probe_corrected": bool(probe and not probe.get("error")),
        "roofline_fraction": (
            max(terms.values()) and
            (mf / chips / TARGET["flops"]) / max(terms.values())),
    })
    return out


def fmt_row(a: dict) -> str:
    if a.get("skipped"):
        return (f"| {a['arch']} | {a['shape']} | — | — | — | — | skipped | "
                f"{a['skipped']} |")
    if a.get("error"):
        return f"| {a['arch']} | {a['shape']} | ERROR: {a['error'][:60]} |"
    return ("| {arch} | {shape} | {t_compute_s:.4f} | {t_memory_s:.4f} | "
            "{t_collective_s:.4f} | {useful_ratio:.2f} | {dominant} | "
            "{roofline_fraction:.2f} |").format(**a)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--in-dir", default="experiments/dryrun")
    ap.add_argument("--probe-dir", default="experiments/probe")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)

    rows = []
    for fn in sorted(glob.glob(os.path.join(args.in_dir, f"*__{args.mesh}.json"))):
        if os.path.basename(fn).startswith("SUMMARY"):
            continue
        with open(fn) as f:
            rec = json.load(f)
        probe = None
        pfn = os.path.join(
            args.probe_dir,
            f"{rec.get('arch')}__{rec.get('shape')}__{args.mesh}.json")
        if os.path.exists(pfn):
            with open(pfn) as f:
                probe = json.load(f)
        rows.append(analyse(rec, probe))
    # the markdown table IS this tool's product: a human-facing report,
    # deliberately outside the machine-readable §14 stdout protocol
    print("| arch | shape | t_compute | t_memory | t_collective | useful "  # repro: noqa=RA003
          "| dominant | roofline_frac |")
    print("|---|---|---|---|---|---|---|---|")  # repro: noqa=RA003
    for a in rows:
        print(fmt_row(a))  # repro: noqa=RA003
    n_probe = sum(1 for a in rows if a.get("probe_corrected"))
    print(f"\n({n_probe}/{len(rows)} cells probe-corrected; times in seconds "  # repro: noqa=RA003
          "per step on 256 chips)")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
