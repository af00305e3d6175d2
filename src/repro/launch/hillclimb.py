"""§Perf hillclimb driver: measure (probe) a cell under a named variant.

    PYTHONPATH=src python -m repro.launch.hillclimb --cell olmoe_train --variant bf16

Variants are (policy, cfg-override, step-options) bundles; each probe reports
scan-aware flops/bytes/collective bytes per device plus the roofline terms, so
every hypothesis->change->measure cycle in EXPERIMENTS.md §Perf is one command.
"""
import argparse
import dataclasses
import json
import os

import jax

from repro.configs import get_arch, get_shape
from repro.core.pcsr import TransPolicy
from repro.core.policy import PRECISION_PRESETS
from repro.launch import costprobe
from repro.launch.config import ServeConfig
from repro.launch.roofline import TARGET, model_flops

CELLS = {
    "olmoe_train": ("olmoe-1b-7b", "train_4k"),
    "zamba_train": ("zamba2-7b", "train_4k"),
    "qwen_decode": ("qwen2.5-14b", "decode_32k"),
    "yi_train": ("yi-34b", "train_4k"),
    "gemma3_decode": ("gemma3-4b", "decode_32k"),
}

VARIANTS = {
    # paper-faithful baseline: FP32 datapath, no posit storage
    "baseline": dict(policy=TransPolicy(), cfg_override={}),
    # TPU-native datapath (paper's FPU=fp32 -> MXU=bf16; DESIGN.md §2)
    "bf16": dict(policy=TransPolicy(compute_dtype="bf16"), cfg_override={}),
    # the paper's technique at the serving bottleneck: posit8 KV cache
    "p8_kv": dict(policy=TransPolicy.from_names(kv_cache="p8_0",
                                                compute_dtype="bf16"),
                  cfg_override={}),
    "p8_kv_f32": dict(policy=TransPolicy.from_names(kv_cache="p8_0"),
                      cfg_override={}),
    # p16 weights at rest (FSDP wire + HBM)
    "p16_weights": dict(policy=TransPolicy.from_names(weights="p16_1",
                                                      compute_dtype="bf16"),
                        cfg_override={}),
    # SSD chunk-size sweep (zamba memory term ∝ chunk length)
    "chunk128": dict(policy=TransPolicy(), cfg_override={"ssm_chunk": 128}),
    "chunk64": dict(policy=TransPolicy(), cfg_override={"ssm_chunk": 64}),
    "chunk128_bf16": dict(policy=TransPolicy(compute_dtype="bf16"),
                          cfg_override={"ssm_chunk": 128}),
}

# Per-layer precision schedules (core/policy.py) as a hillclimb search
# dimension: every preset becomes a variant (over the bf16 datapath), and
# --precision-policy overlays any preset/spec onto any variant's policy
# (accepting @artifact.json to probe a saved calibration).
VARIANTS.update({
    f"prec_{name.replace('-', '_')}": dict(
        policy=pol.with_base(dataclasses.replace(
            pol.base, compute_dtype="bf16")),
        cfg_override={})
    for name, pol in PRECISION_PRESETS.items()
})

# Data-driven schedule (repro.calib, DESIGN.md §11): calibrate on the cell's
# *reduced* config (cheap — a couple of observed forward passes), then probe
# the full-size cell under the emitted per-layer dynamic-es policy.  Layer
# paths are size-independent, so reduced-model rules transfer verbatim.
VARIANTS["prec_calibrated"] = dict(policy="__calibrated__", cfg_override={})


def _calibrated_policy(cfg):
    import jax
    import numpy as np

    from repro.calib.search import calibrate_model, calibration_batches
    from repro.models.registry import build_model

    rcfg = cfg.reduced()
    model = build_model(rcfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    base = TransPolicy(compute_dtype="bf16")
    batches = calibration_batches(rcfg, rng, 2, batch=2, seq=64)
    policy, _ = calibrate_model(
        lambda b: model.loss(params, b, base)[0], batches, params,
        base=base, name=f"calibrated-{rcfg.name}")
    return policy


def run_variant(cell: str, variant: str,
                precision_policy: str | None = None) -> dict:
    arch, shape_name = CELLS[cell]
    v = VARIANTS[variant]
    cfg = get_arch(arch)
    if v["cfg_override"]:
        cfg = dataclasses.replace(cfg, **v["cfg_override"])
    policy = v["policy"]
    if policy == "__calibrated__":
        if precision_policy:
            # the overlay below replaces the rule schedule wholesale —
            # running the calibration first would only throw its result away
            policy = TransPolicy(compute_dtype="bf16")
        else:
            policy = _calibrated_policy(cfg)
    if precision_policy:
        # overlay a per-layer weight schedule onto the variant's base policy
        # (resolution shared with serve.py via ServeConfig.build_policy)
        base = policy.base if hasattr(policy, "base") else policy
        policy, _ = ServeConfig(arch=arch, precision_policy=precision_policy,
                                codec_impl=base.codec_impl,
                                epilogue=base.epilogue,
                                attn_impl=base.attn_impl).build_policy(base)

    # monkey-patch costprobe's binding so probe_cell sees the override
    orig = costprobe.get_arch

    def _arch_override(name):
        return cfg if name == arch else orig(name)

    costprobe.get_arch = _arch_override
    try:
        res = costprobe.probe_cell(arch, shape_name, policy=policy)
    finally:
        costprobe.get_arch = orig

    shape = get_shape(shape_name)
    chips = res["n_chips"]
    t_c = res["flops_per_device"] / TARGET["flops"]
    t_m = res["bytes_per_device"] / TARGET["hbm_bw"]
    t_x = res["coll_per_device"] / TARGET["ici_bw"]
    mf = model_flops(cfg, shape)
    res.update({
        "variant": variant, "cell": cell,
        "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_x,
        "dominant": max({"compute": t_c, "memory": t_m, "collective": t_x},
                        key=lambda k: {"compute": t_c, "memory": t_m,
                                       "collective": t_x}[k]),
        "model_flops": mf,
        "roofline_fraction": (mf / chips / TARGET["flops"]) / max(t_c, t_m, t_x)
        if max(t_c, t_m, t_x) else 0.0,
    })
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, choices=sorted(CELLS))
    ap.add_argument("--variant", required=True, choices=sorted(VARIANTS))
    ap.add_argument("--precision-policy", default=None,
                    help="per-layer weight schedule overlay: preset name or "
                         "pattern=fmt[:packed],... spec (core/policy.py)")
    ap.add_argument("--out-dir", default="experiments/hillclimb")
    args = ap.parse_args(argv)
    res = run_variant(args.cell, args.variant,
                      precision_policy=args.precision_policy)
    print(json.dumps({"kind": "hillclimb/result",
                      **{k: v for k, v in res.items()
                         if not isinstance(v, (list, dict))}}))
    os.makedirs(args.out_dir, exist_ok=True)
    tag = f"{args.cell}__{args.variant}"
    if args.precision_policy:
        tag += f"__{args.precision_policy.replace('*', '_').replace('/', '_')}"
    with open(os.path.join(args.out_dir, f"{tag}.json"), "w") as f:
        json.dump(res, f, indent=1)


if __name__ == "__main__":
    # the production meshes need 512 devices: virtual ones on the CPU
    jax.config.update("jax_num_cpu_devices", 512)
    main()
