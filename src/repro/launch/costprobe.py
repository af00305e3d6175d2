"""Scan-aware cost probes for the roofline (EXPERIMENTS.md §Roofline).

XLA's HLO cost analysis counts while-loop bodies once, so scanned stacks
under-report FLOPs/bytes/collectives by their trip counts. Probes fix this by
measurement, not modeling: lower fully-UNROLLED reduced-depth variants of each
cell at two depths d1 < d2, take the per-period delta, and extrapolate
linearly to the full depth — exact for homogeneous layer stacks:

    C_full = C(d1) + delta * (units_full - units(d1)),
    delta = (C(d2) - C(d1)) / (units(d2) - units(d1))

Depths step in whole heterogeneity periods (gemma3: 6 = 5 local + 1 global;
zamba: 6 mamba + 1 shared; xlstm: 4 = 3 mLSTM + 1 sLSTM), so the delta
captures one full period. Train probes run microbatches=1 (total FLOPs/bytes
are microbatch-invariant; collectives differ <~1/micro in the accumulate sums).

    PYTHONPATH=src python -m repro.launch.costprobe --arch yi-34b --shape train_4k
"""
import argparse
import dataclasses
import json
import os
import sys

import jax

from repro.configs import get_arch, get_shape
from repro.core.pcsr import TransPolicy
from repro.launch.mesh import make_production_mesh
from repro.launch.dryrun import lower_cell, parse_collectives
from repro.models.unroll import unroll_mode


def _probe_plan(cfg):
    """(period, depths, units_full) per family."""
    if cfg.family == "gemma3":
        period = cfg.local_ratio + 1
        return period, (period, 2 * period), cfg.n_layers / period
    if cfg.family == "zamba":
        period = cfg.shared_attn_every
        return period, (period, 2 * period), cfg.n_layers / period
    if cfg.family == "xlstm":
        period = cfg.slstm_every
        return period, (period, 2 * period), cfg.n_layers / period
    # dense / moe / vlm / whisper: homogeneous
    return 1, (2, 4), float(cfg.n_layers)


def _probe_cfg(cfg, depth: int):
    kw = {"n_layers": depth}
    if cfg.family == "whisper":
        kw["enc_layers"] = depth
    return dataclasses.replace(cfg, **kw)


def _measure(cfg, shape, mesh, policy, grad_sync):
    with unroll_mode():
        lowered = lower_cell(cfg, shape, mesh, policy=policy,
                             grad_sync=grad_sync, force_micro=1)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text())
    return {
        "flops": cost.get("flops", 0.0),
        "bytes": cost.get("bytes accessed", 0.0),
        "coll": sum(v["bytes"] for v in coll.values()),
        "coll_by_op": {k: v["bytes"] for k, v in coll.items()},
    }


def probe_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               policy: TransPolicy = None, grad_sync: str = "gspmd") -> dict:
    policy = policy or TransPolicy()
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    period, (d1, d2), units_full = _probe_plan(cfg)

    c1 = _measure(_probe_cfg(cfg, d1), shape, mesh, policy, grad_sync)
    c2 = _measure(_probe_cfg(cfg, d2), shape, mesh, policy, grad_sync)
    u1, u2 = d1 / period, d2 / period

    out = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
           "n_chips": mesh.size, "policy": policy.describe(),
           "grad_sync": grad_sync,
           "probe_depths": [d1, d2], "units_full": units_full}
    for key in ("flops", "bytes", "coll"):
        delta = (c2[key] - c1[key]) / (u2 - u1)
        out[key + "_per_device"] = c1[key] + delta * (units_full - u1)
        out[key + "_probe"] = [c1[key], c2[key]]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=False)
    ap.add_argument("--shape", required=False)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--policy", default="none")
    ap.add_argument("--grad-sync", default="gspmd")
    ap.add_argument("--out-dir", default="experiments/probe")
    args = ap.parse_args(argv)

    from repro.configs import cells
    todo = ([(c.name, s.name) for c, s, _ in cells()] if args.all
            else [(args.arch, args.shape)])
    for arch, shape in todo:
        try:
            res = probe_cell(arch, shape, multi_pod=args.multi_pod,
                             policy=TransPolicy.from_spec(args.policy),
                             grad_sync=args.grad_sync)
        except Exception as e:
            res = {"arch": arch, "shape": shape,
                   "error": f"{type(e).__name__}: {str(e)[:200]}"}
            print(f"[FAIL] {arch}|{shape}: {res['error']}", file=sys.stderr)
        print(json.dumps({"kind": "costprobe/cell", **res}))
        if args.out_dir:
            os.makedirs(args.out_dir, exist_ok=True)
            mode = "multi" if args.multi_pod else "single"
            tag = f"{arch}__{shape}__{mode}"
            if args.policy != "none":
                tag += "__" + args.policy.replace(",", "_").replace("=", "-")
            if args.grad_sync != "gspmd":
                tag += "__" + args.grad_sync
            with open(os.path.join(args.out_dir, tag + ".json"), "w") as f:
                json.dump(res, f, indent=1)


if __name__ == "__main__":
    # the production meshes need 512 devices: virtual ones on the CPU
    jax.config.update("jax_num_cpu_devices", 512)
    main()
