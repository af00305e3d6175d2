"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract the roofline inputs from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod --out-dir experiments/dryrun

Per cell this prints (and JSON-dumps):
  * compiled.memory_analysis()   — proves the per-device footprint fits
  * compiled.cost_analysis()     — HLO FLOPs / bytes for §Roofline
  * the collective schedule      — op counts + payload bytes by dtype,
                                   parsed from the post-SPMD optimized HLO

Run as a program, it gives the CPU backend 512 virtual devices
(``jax_num_cpu_devices``); importing it changes nothing.
"""
import argparse
import json
import os
import re
import sys
import time
from collections import defaultdict

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_arch, get_shape, list_archs
from repro.configs.base import ModelCfg, ShapeCfg
from repro.core.pcsr import TransPolicy
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (batch_specs, cache_specs, decode_token_spec,
                                   tree_param_specs, tree_shardings)
from repro.launch.steps import (abstract_batch, abstract_cache, abstract_params,
                                make_decode_step, make_prefill_step,
                                make_opt_state, make_train_step)
from repro.models.registry import build_model
from repro.models.shardhooks import activation_sharding
from repro.optim import AdamWConfig


def make_sp_hook(mesh):
    """Sequence-parallel activation constraints (DESIGN.md §5, SP):
    the residual stream (B, S, D) shards S over "model" between blocks, so
    remat-saved layer checkpoints shrink by the TP degree."""
    from repro.launch.mesh import batch_axes
    dp = batch_axes(mesh)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    n_tp = mesh.shape["model"]

    from jax.sharding import PartitionSpec as P

    def hook(x, kind):
        if kind == "expert_buffers" and x.ndim == 3:
            e = "model" if x.shape[0] % n_tp == 0 else None
            c = "data" if x.shape[1] % mesh.shape["data"] == 0 else None
            return jax.lax.with_sharding_constraint(x, P(e, c, None))
        if kind != "residual" or x.ndim != 3:
            return x
        b = dp if (x.shape[0] % n_dp == 0 and x.shape[0] >= n_dp) else None
        s = "model" if (x.shape[1] % n_tp == 0 and x.shape[1] >= n_tp) else None
        if b is None and s is None:
            return x
        return jax.lax.with_sharding_constraint(x, P(b, s, None))

    return hook

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
}
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_TYPE_RE = re.compile(r"\b(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|s32|s16|s8|"
                      r"u64|u32|u16|u8|pred)\[([0-9,]*)\]")


def parse_collectives(hlo_text: str) -> dict:
    """Sum payload bytes of every collective op in the optimized (post-SPMD,
    per-device) HLO. Payload = result-shape bytes (receive volume bound)."""
    stats = defaultdict(lambda: {"count": 0, "bytes": 0, "by_dtype": defaultdict(int)})
    for line in hlo_text.splitlines():
        ls = line.strip()
        if ls.startswith("ROOT "):
            ls = ls[5:]
        m = re.match(r"%?[\w.\-]+\s*=\s*(.*)$", ls)
        if not m:
            continue
        rhs = m.group(1)
        op = None
        for c in _COLLECTIVES:
            if re.search(rf"\b{c}(-start|-done)?\(", rhs):
                op = c
                break
        if op is None or re.search(rf"\b{op}-done\(", rhs):
            continue  # count -start, skip -done (same payload)
        lhs_types = rhs.split(op)[0]
        total = 0
        for dt, dims in _TYPE_RE.findall(lhs_types):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
            stats[op]["by_dtype"][dt] += n * _DTYPE_BYTES[dt]
        stats[op]["count"] += 1
        stats[op]["bytes"] += total
    return {k: {"count": v["count"], "bytes": v["bytes"],
                "by_dtype": dict(v["by_dtype"])} for k, v in stats.items()}


def lower_cell(cfg: ModelCfg, shape: ShapeCfg, mesh, *,
               policy: TransPolicy, grad_sync: str = "gspmd",
               force_micro: int | None = None):
    """Build + lower the step function for one cell. Returns (lowered, meta)."""
    model = build_model(cfg)
    params_abs = abstract_params(model)
    p_specs = tree_param_specs(params_abs, mesh)
    p_shard = tree_shardings(p_specs, mesh)

    if shape.kind == "train":
        opt_cfg = AdamWConfig(moment_fmt=policy.optimizer)
        opt_abs = make_opt_state(model, opt_cfg)
        o_specs = tree_param_specs(opt_abs, mesh)  # moments mirror params
        o_shard = tree_shardings(o_specs, mesh)
        batch_abs = abstract_batch(cfg, shape)
        b_shard = tree_shardings(batch_specs(cfg, shape, mesh), mesh)
        b_shard = {k: b_shard[k] for k in batch_abs}
        # microbatch so each device sees ~16k tokens per accumulation step
        n_dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        tokens_per_dev = shape.global_batch * shape.seq_len // n_dp
        micro = max(1, min(8, tokens_per_dev // 16384,
                           shape.global_batch // n_dp))
        if force_micro is not None:
            micro = force_micro
        step_fn = make_train_step(
            model, policy, opt_cfg, grad_sync=grad_sync, mesh=mesh,
            grad_fmt=policy.gradients, microbatches=micro)
        jitted = jax.jit(
            step_fn,
            in_shardings=(p_shard, o_shard, b_shard, None),
            out_shardings=(p_shard, o_shard, None),
            donate_argnums=(0, 1),
        )
        args = (params_abs, opt_abs,
                {k: batch_abs[k] for k in batch_abs},
                jax.ShapeDtypeStruct((), jnp.int32))
    elif shape.kind == "prefill":
        batch_abs = abstract_batch(cfg, shape)
        b_shard = tree_shardings(batch_specs(cfg, shape, mesh), mesh)
        b_shard = {k: b_shard[k] for k in batch_abs}
        cache_abs = abstract_cache(model, cfg, shape, policy)
        c_shard = tree_shardings(cache_specs(cache_abs, cfg, mesh), mesh)
        step_fn = make_prefill_step(model, cfg, policy, shape)
        jitted = jax.jit(step_fn, in_shardings=(p_shard, b_shard),
                         out_shardings=(None, c_shard))
        args = (params_abs, batch_abs)
    elif shape.kind == "decode":
        cache_abs = abstract_cache(model, cfg, shape, policy)
        c_shard = tree_shardings(cache_specs(cache_abs, cfg, mesh), mesh)
        tok_abs = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
        t_shard = jax.NamedSharding(mesh, decode_token_spec(cfg, shape, mesh))
        step_fn = make_decode_step(model, cfg, policy)
        jitted = jax.jit(
            step_fn,
            in_shardings=(p_shard, t_shard, c_shard),
            out_shardings=(None, c_shard),
            donate_argnums=(2,),
        )
        args = (params_abs, tok_abs, cache_abs)
    else:
        raise ValueError(shape.kind)

    with mesh, activation_sharding(make_sp_hook(mesh)):
        lowered = jitted.lower(*args)
    return lowered


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             policy: TransPolicy, grad_sync: str = "gspmd",
             collect_hlo: bool = True) -> dict:
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size

    if shape.name == "long_500k" and not cfg.supports_long_context:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "skipped": "no sub-quadratic path (DESIGN.md §6)"}

    t0 = time.perf_counter()
    lowered = lower_cell(cfg, shape, mesh, policy=policy, grad_sync=grad_sync)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    print(mem, file=sys.stderr)
    cost = compiled.cost_analysis()
    print({k: v for k, v in cost.items()
           if k in ("flops", "bytes accessed") and isinstance(v, (int, float))},
          file=sys.stderr)

    coll = {}
    if collect_hlo:
        txt = compiled.as_text()
        coll = parse_collectives(txt)
        del txt

    result = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "multi_pod": multi_pod, "n_chips": n_chips,
        "grad_sync": grad_sync, "policy": policy.describe(),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": cost.get("flops", 0.0),
        "bytes_per_device": cost.get("bytes accessed", 0.0),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        "collectives": coll,
    }
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--grad-sync", default="gspmd",
                    choices=["gspmd", "posit_pod"])
    ap.add_argument("--policy", default="none",
                    help="none | p16-train | p8-serve | weights=p8_0,kv=p8_0,...")
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--no-hlo", action="store_true",
                    help="skip collective parsing (faster)")
    args = ap.parse_args(argv)

    policy = TransPolicy.from_spec(args.policy)
    cells = []
    if args.all:
        for a in list_archs():
            for s in SHAPES:
                cells.append((a, s.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    ok = True
    for arch, shape in cells:
        tag = f"{arch}|{shape}|{'multi' if args.multi_pod else 'single'}"
        try:
            res = run_cell(arch, shape, multi_pod=args.multi_pod,
                           policy=policy, grad_sync=args.grad_sync,
                           collect_hlo=not args.no_hlo)
        except Exception as e:  # a failing cell is a bug in our sharding
            ok = False
            res = {"arch": arch, "shape": shape, "multi_pod": args.multi_pod,
                   "error": f"{type(e).__name__}: {e}"}
            print(f"[FAIL] {tag}: {res['error']}", file=sys.stderr)
        print(json.dumps({"kind": "dryrun/cell",
                          **{k: v for k, v in res.items()
                             if k != "collectives"}}))
        if args.out_dir:
            os.makedirs(args.out_dir, exist_ok=True)
            mode = "multi" if args.multi_pod else "single"
            fn = os.path.join(args.out_dir, f"{arch}__{shape}__{mode}.json")
            with open(fn, "w") as f:
                json.dump(res, f, indent=1)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    # the production meshes need 512 devices: virtual ones on the CPU
    jax.config.update("jax_num_cpu_devices", 512)
    main()
