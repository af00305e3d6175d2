"""Serving driver: static batched prefill+decode, or continuous batching.

    # static (lockstep) batch
    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --reduced \
        --batch 4 --prompt-len 32 --gen 16 --policy p8-serve

    # continuous batching over the ragged posit KV cache (launch/engine.py)
    PYTHONPATH=src python -m repro.launch.serve --arch yi-34b --reduced \
        --continuous --max-slots 4 --arrival-rate 8 --requests 16 \
        --policy p8-serve --attn-impl kernel

Reports tokens/s and the KV-cache HBM footprint under the selected pcsr policy
(the paper's Table-IV memory savings, at the serving bottleneck).  Decode
throughput is measured *warm*: the first decode step (jit compile) is timed
separately as ``compile_s`` and excluded from ``decode_tok_per_s``.

``--attn-impl`` selects the decode attention dispatch (DESIGN.md §10):
``kernel`` routes every step through the flash-decode front door
(``kernels.posit_attention.ops`` — Pallas on TPU, length-bounded tiled XLA
elsewhere), ``xla`` keeps the full-cache-decode einsum, ``auto`` picks per
layer.  ``--codec-impl`` selects the codec lowering (auto | lut | bits) and
``--epilogue`` the layer dataflow (fused | chained).

``--precision-policy`` schedules *per-layer* weight formats over the base
policy (core/policy.py) — a preset name, a ``pattern=fmt[@es][:packed]``
spec, or ``@path.json`` to load a saved calibration artifact;
``--quantize-weights`` converts the float weights to real posit storage under
that schedule and reports the weight-byte savings.

``--calibrate N`` runs the repro.calib pipeline (DESIGN.md §11) before
serving: N observed forward passes stream per-layer weight/activation
histograms, the analytic posit error model scores every (p8|p16) x es
candidate, and the byte-budgeted search (``--weight-byte-budget``, default
1 byte/weight — the p8 floor) emits the per-layer dynamic-es policy the run
then serves under.  ``--policy-out cal.json`` saves the artifact for
``--precision-policy @cal.json`` reuse::

    PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b \
        --reduced --calibrate 4 --policy-out cal.json --quantize-weights

Observability (DESIGN.md §12): ``--metrics-out m.json`` writes the engine's
metrics snapshot (plus a ``m.prom`` Prometheus text exposition alongside),
``--trace-out t.json`` a Chrome-trace/Perfetto request timeline, and
``--numerics-watch N`` probes every N-th decode step for posit saturation /
underflow / NaR rates and calibration drift (baselines come from a
``--precision-policy @cal.json`` artifact or a fresh ``--calibrate`` run)::

    PYTHONPATH=src python -m repro.launch.serve --arch yi-34b --reduced \
        --continuous --precision-policy @cal.json --numerics-watch 8 \
        --metrics-out metrics.json --trace-out trace.json

Every stdout line is one JSON object tagged with a ``"kind"`` key
(``serve/prefill``, ``serve/calibration``, ``serve/policy-out``,
``serve/numerics``, ``serve/report``) so consumers filter by kind instead of
guessing by field names.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.config import (ServeConfig, add_cli_args, config_from_args,
                                 use_compile_cache)
from repro.launch.engine import (KV_CONTAINERS as _KV_CONTAINERS, Request,
                                 poisson_requests)
from repro.models.layers import policy_weight_bytes, quantize_params
from repro.obs.metrics import percentile_ms


def cache_bytes(cache) -> int:
    """Total bytes of every array in the cache (bookkeeping included)."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache)
               if hasattr(x, "size"))


def kv_cache_bytes(cache) -> int:
    """Bytes of the K/V arrays only.

    ``len``/``pos``/``lens`` bookkeeping and recurrent state (ssm / xlstm /
    quire carries) are not KV cache and must not inflate the paper's
    kv-bytes-per-token claim — only leaves named ``k``/``v`` inside a KV
    container count.
    """
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        keys = [p.key for p in path if hasattr(p, "key")]
        if keys and keys[-1] in ("k", "v") \
                and any(k in _KV_CONTAINERS for k in keys[:-1]):
            total += leaf.size * leaf.dtype.itemsize
    return total


def _serve_static(args, cfg, model, params, policy, rng, S_max):
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)))
    decode = jax.jit(lambda p, t, c: model.decode_step(p, t, c, policy))
    compile_s = None

    if cfg.family == "whisper":
        batch = {"frames": jnp.asarray(rng.normal(
            0, 1, (args.batch, cfg.enc_frames, cfg.d_model)).astype(np.float32)),
            "tokens": tokens}
        t0 = time.perf_counter()
        cache = model.init_cache(params, batch, policy, S_max)
        # teacher-force the full decoder prompt: every prompt token passes
        # through decode_step (the old path fed tokens[:, 0] and silently
        # dropped the rest of the prompt).  The first step pays jit compile;
        # time it apart so prefill_s stays a throughput number.
        tc = time.perf_counter()
        logits, cache = decode(params, tokens[:, 0], cache)
        jax.block_until_ready(logits)
        compile_s = time.perf_counter() - tc
        for i in range(1, args.prompt_len):
            logits, cache = decode(params, tokens[:, i], cache)
        jax.block_until_ready(logits)
        print(json.dumps({
            "kind": "serve/prefill",
            "prefill_s": round(time.perf_counter() - t0 - compile_s, 3)}))
    else:
        kw = {}
        if cfg.family == "vlm":
            kw["patch_embeds"] = jnp.asarray(rng.normal(
                0, 1, (args.batch, cfg.n_patches, cfg.d_model)).astype(np.float32))
        t0 = time.perf_counter()
        logits, cache = model.prefill(params, tokens, policy, S_max=S_max, **kw)
        print(json.dumps({"kind": "serve/prefill",
                          "prefill_s": round(time.perf_counter() - t0, 3)}))

    tok = jnp.argmax(logits, -1)
    out_tokens = [tok]
    timed_steps = args.gen - 1
    if compile_s is None:
        # warm up one step before the throughput clock: the first decode call
        # pays jit compile, which used to be silently folded into tokens/s
        # (whisper is already warm from teacher-forcing the prompt)
        t0 = time.perf_counter()
        logits, cache = decode(params, tok, cache)
        tok = jnp.argmax(logits, -1)
        jax.block_until_ready(tok)
        compile_s = time.perf_counter() - t0
        out_tokens.append(tok)
        timed_steps -= 1

    timed_steps = max(timed_steps, 0)
    t0 = time.perf_counter()
    for _ in range(timed_steps):
        logits, cache = decode(params, tok, cache)
        tok = jnp.argmax(logits, -1)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    dt = max(time.perf_counter() - t0, 1e-9)

    return {
        "mode": "static",
        "decode_tok_per_s": round(args.batch * timed_steps / dt, 1),
        "compile_s": round(compile_s, 3),
        "sample_tokens": np.stack([np.asarray(t) for t in out_tokens], 1)[0][:8]
        .tolist(),
    }, cache


def _build_observability(args, policy, drift_meta):
    """(metrics, tracer, numerics) sinks from the CLI flags (None = off).

    Drift baselines come from ``drift_meta`` — the calibration artifact dict
    (``--precision-policy @cal.json``) or the fresh ``--calibrate`` search
    report — when it carries per-site ``act_hist`` blocks; without them the
    watcher still reports saturation/underflow/NaR, just no drift scores.
    """
    metrics = tracer = numerics = None
    if args.metrics_out:
        from repro.obs.metrics import MetricsRegistry
        metrics = MetricsRegistry()
    if args.trace_out:
        from repro.obs.trace import TraceRecorder
        tracer = TraceRecorder()
    if args.numerics_watch:
        from repro.obs.numerics import NumericsWatcher, load_baselines
        baselines = load_baselines(drift_meta) if drift_meta else {}
        numerics = NumericsWatcher(policy=policy, baselines=baselines,
                                   every=args.numerics_watch)
    return metrics, tracer, numerics


def _serve_continuous(args, cfg, model, params, policy, rng, S_max,
                      obs=(None, None, None)):
    if model.prefill is None:
        sys.exit(f"--continuous needs a prefill entry point "
                 f"(family {cfg.family!r} has none)")
    max_slots = args.max_slots or args.batch
    n_req = args.requests or 2 * max_slots
    prefill_kwargs = None
    if cfg.family == "vlm":
        patches = jnp.asarray(rng.normal(
            0, 1, (1, cfg.n_patches, cfg.d_model)).astype(np.float32))
        prefill_kwargs = lambda req: {"patch_embeds": patches}  # noqa: E731

    metrics, tracer, numerics = obs
    # fault-tolerance plane (repro.ft.serving, DESIGN.md §13)
    snapshotter = watchdog = preemption = straggler = None
    if args.snapshot_every:
        from repro.ft import EngineSnapshotter, PreemptionSignal
        snapshotter = EngineSnapshotter(
            args.snapshot_dir, every=args.snapshot_every, metrics=metrics)
        # SIGTERM -> finish the in-flight step, drain, force-snapshot, exit
        preemption = PreemptionSignal(install_sigterm=True)
    if args.degrade:
        from repro.ft import DegradationController

        def _log_event(ev):
            print(json.dumps({"kind": "serve/degrade", **ev}))
        watchdog = DegradationController(numerics, metrics=metrics,
                                         on_event=_log_event)
    if metrics is not None:
        from repro.ft import StragglerMonitor
        straggler = StragglerMonitor()

    eng = args.build_engine(
        model, params, policy, prefill_kwargs=prefill_kwargs,
        metrics=metrics, tracer=tracer, numerics=numerics,
        snapshotter=snapshotter, watchdog=watchdog)

    # warm the executables (prefill at the prompt length + the grid decode;
    # 2 steps so the numerics-probed twin AND the plain decode both compile)
    # before the serving clock starts; report compile time separately
    t0 = time.perf_counter()
    eng.submit(Request(rid=-1, prompt=np.zeros((args.prompt_len,), np.int32),
                       max_new_tokens=min(3, args.gen)))
    eng.admit()
    eng.step()
    eng.step()
    eng.reset(seed=args.seed)
    if numerics is not None:
        numerics.rebase()   # drop the warmup probe from the drift window
    compile_s = time.perf_counter() - t0
    if args.chaos_preempt_step is not None:
        # attach AFTER warmup: the warmup steps run under the same step
        # counter and must not consume the trigger
        from repro.ft import FaultPlan
        eng.faults = FaultPlan(preempt_at_step=args.chaos_preempt_step,
                               use_sigterm=True)

    # resume AFTER warmup/reset so the restored state lands in already-
    # compiled executables and nothing of the dummy request survives
    restored = False
    if args.resume and snapshotter is not None:
        restored = snapshotter.restore_into(eng, now=0.0)
        if restored:
            print(json.dumps({
                "kind": "serve/resume", "steps": eng.steps,
                "active_slots": int(eng.active.sum()),
                "queued": len(eng.queue),
                "done": len(eng.completions)}))

    if restored:
        # the snapshot carries the full remaining workload (a preempted run
        # drains every unsubmitted request into the queue before saving)
        reqs = []
    else:
        reqs = poisson_requests(
            n_req, arrival_rate=args.arrival_rate,
            prompt_lens=(args.prompt_len,),
            max_new_tokens=args.gen, vocab=cfg.vocab, seed=args.seed)
    t0 = time.perf_counter()
    try:
        completions = eng.run(reqs, preemption=preemption,
                              straggler=straggler)
    finally:
        if snapshotter is not None:
            snapshotter.close()    # surface any pending async save failure
    makespan = max(time.perf_counter() - t0, 1e-9)

    n_tokens = sum(len(c.tokens) for c in completions)
    per_tok = [t for c in completions for t in c.per_token_s()]
    report = {
        "mode": "continuous",
        "requests": len(completions),
        "tokens": n_tokens,
        "finish_reasons": dict(collections.Counter(
            c.finish_reason for c in completions)),
        "max_slots": max_slots,
        "arrival_rate": args.arrival_rate,
        "decode_tok_per_s": round(n_tokens / makespan, 1),
        "decode_steps": eng.steps,
        "compile_s": round(compile_s, 3),
        "p50_token_ms": percentile_ms(per_tok, 50),
        "p95_token_ms": percentile_ms(per_tok, 95),
        "p50_queue_ms": percentile_ms([c.queue_s for c in completions], 50),
        "sample_tokens": completions[0].tokens[:8] if completions else [],
    }
    if snapshotter is not None:
        report["snapshots"] = snapshotter.saves
        report["resumed"] = restored
        report["preempted"] = bool(preemption and preemption.triggered)
        report["in_flight_at_exit"] = int(eng.active.sum()) + len(eng.queue)
    if watchdog is not None:
        report["degradations"] = len(watchdog.events)
    if hasattr(eng, "prefix_stats"):
        report["prefix_cache"] = eng.prefix_stats()
    return report, eng.cache


def _calibrate(args, cfg, model, params, policy):
    """observe -> search -> (optionally) persist; returns (policy, report).

    The emitted PrecisionPolicy keeps ``policy``'s non-weight roles
    (kv_cache, compute dtype, codec/epilogue/attn dispatch) as its base; any
    ``--precision-policy`` rules are superseded by the calibrated schedule.
    The report doubles as the drift baseline for ``--numerics-watch``.
    """
    from repro.calib.search import (calibrate_model, calibration_batches,
                                    save_artifact)

    base = policy.base if hasattr(policy, "base") else policy
    rng = np.random.default_rng(args.seed)
    batches = calibration_batches(cfg, rng, args.calibrate,
                                  batch=args.batch, seq=args.prompt_len)
    # drive model.loss, not forward: the loss graph reaches the lm_head /
    # logits projection, which serving decodes through every step
    cal_policy, report = calibrate_model(
        lambda b: model.loss(params, b, base)[0], batches, params,
        base=base, byte_budget=args.weight_byte_budget,
        name=f"calibrated-{cfg.name}")
    print(json.dumps({"kind": "serve/calibration", "calibration": {
        k: report[k] for k in ("n_sites", "p8_floor_bytes", "byte_budget",
                               "weight_bytes", "predicted_err_score")}}))
    if args.policy_out:
        save_artifact(args.policy_out, cal_policy, report)
        print(json.dumps({"kind": "serve/policy-out",
                          "policy_out": args.policy_out}))
    return cal_policy, report


def main(argv=None):
    # the CLI is generated from the ServeConfig schema (launch/config.py):
    # one flag per field; --config loads a saved document and explicitly-
    # passed flags override it
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None, metavar="CFG.json",
                    help="ServeConfig JSON document (kind repro/serve-config"
                         "); explicitly-passed flags override its fields")
    add_cli_args(ap)
    ns = ap.parse_args(argv)
    try:
        base = ServeConfig.load(ns.config) if ns.config else None
        args = config_from_args(ns, base=base).validate()
    except (ValueError, OSError) as e:
        ap.error(str(e))
    run(args)


def run(args: ServeConfig) -> dict:
    """Serve under a validated :class:`ServeConfig` (the programmatic entry
    point — hillclimb and tests call this with a constructed config).
    Returns the ``serve/report`` record it prints (without its kind)."""
    policy, drift_meta = args.build_policy()
    model, params = args.load_model(policy)
    cfg = model.cfg
    if args.calibrate:
        policy, cal_report = _calibrate(args, cfg, model, params, policy)
        drift_meta = {"meta": cal_report}
        if args.quantize_weights:
            params = quantize_params(params, policy)
    weight_report = {}
    if args.quantize_weights:
        weight_report = policy_weight_bytes(
            jax.eval_shape(model.init, jax.random.key(args.seed)), policy)
    S_max = args.s_max(cfg)

    metrics, tracer, numerics = _build_observability(args, policy, drift_meta)
    profiler = None
    if args.profile_out:
        from repro.obs import prof
        profiler = prof.KernelProfiler()
    rng = np.random.default_rng(args.seed)
    # telemetry flushes in finally: a crash (or an injected fault) mid-serve
    # must still leave the metrics snapshot / trace on disk for post-mortem
    try:
        with contextlib.ExitStack() as stack:
            if profiler is not None:
                stack.enter_context(prof.profiling(profiler))
            t_serve0 = time.perf_counter()
            if args.continuous:
                report, cache = _serve_continuous(
                    args, cfg, model, params, policy, rng, S_max,
                    obs=(metrics, tracer, numerics))
                n_rows = args.max_slots or args.batch
            else:
                report, cache = _serve_static(args, cfg, model, params,
                                              policy, rng, S_max)
                n_rows = args.batch
            serve_s = time.perf_counter() - t_serve0

        if profiler is not None:
            prep = profiler.save(args.profile_out, measured_total_s=serve_s)
            print(json.dumps({"kind": "serve/profile",
                              "profile_out": args.profile_out,
                              "rows": len(prep["rows"]),
                              "dispatches": prep["totals"]["dispatches"],
                              "bytes": prep["totals"]["bytes"],
                              "bound_s": prep["totals"]["bound_s"],
                              "measured_s": round(serve_s, 4)}))

        if numerics is not None:
            nrep = numerics.report()
            print(json.dumps({"kind": "serve/numerics",
                              "recalibrate": nrep["recalibrate"],
                              "probes": nrep["probes"],
                              "max_drift_score": nrep["max_drift_score"]}))
            if metrics is not None:
                metrics.set_context(numerics=nrep)
        if metrics is not None:
            metrics.set_context(arch=cfg.name, policy=policy.describe(),
                                mode=report.get("mode") if args.continuous
                                else "static")

        kv_b = kv_cache_bytes(cache)
        out = {
            "arch": cfg.name, "policy": policy.describe(),
            **report,
            "kv_cache_bytes": kv_b,
            "cache_bytes_total": cache_bytes(cache),
            "kv_bytes_per_token": kv_b // (n_rows * S_max),
            **weight_report,
            "config": args.to_json(),
        }
        print(json.dumps({"kind": "serve/report", **out}))
        return out
    finally:
        if metrics is not None:
            metrics.save(args.metrics_out)
            with open(args.metrics_out + ".prom", "w") as f:
                f.write(metrics.prometheus())
        if tracer is not None:
            tracer.save(args.trace_out)


if __name__ == "__main__":
    use_compile_cache()
    main()
