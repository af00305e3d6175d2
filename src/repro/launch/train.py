"""Training driver — CPU-runnable at reduced scale, production flags for pods.

    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --reduced \
        --steps 100 --batch 8 --seq 128 --policy p16-train --ckpt-dir /tmp/ck

Wires together every substrate: config -> model -> policy -> data pipeline ->
AdamW (posit moments optional) -> FT loop (async checkpoints, preemption,
straggler monitor, auto-resume) -> observability (DESIGN.md §16):

* ``--telemetry-every N`` compiles a second, *probed* train-step executable
  (``make_train_step(..., telemetry=True)`` traced under the telemetry
  observer) and routes every N-th step through it — gradient + activation
  binade histograms, update/param ratio, nonfinite counts, drift detection
  against ``--calibration`` (or the run's own first window).  Emits
  ``train/telemetry`` per probe and ``train/drift`` when a site latches.
* ``--metrics-out`` writes the metrics-registry JSON snapshot (+ ``.prom``
  Prometheus exposition alongside) merged with the telemetry report.
* ``--trace-out`` writes a Chrome trace of step spans (probes marked).
* ``--profile-out`` runs one profiled step after training and writes the
  per-kernel roofline-attribution report (JSON + ``.md`` table).
* ``--step-log`` appends the bounded per-step JSONL log (off the step path).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.core.pcsr import TransPolicy
from repro.checkpoint.ckpt import CheckpointManager
from repro.data.pipeline import SyntheticLMPipeline
from repro.ft.runtime import FaultTolerantLoop, PreemptionSignal
from repro.launch.config import use_compile_cache
from repro.launch.steps import make_train_step
from repro.models.registry import build_model
from repro.optim import AdamWConfig, adamw_init


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--policy", default="none")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default=None,
                    help="metrics snapshot JSON (+ .prom exposition)")
    ap.add_argument("--trace-out", default=None,
                    help="Chrome trace of step spans")
    ap.add_argument("--profile-out", default=None,
                    help="per-kernel roofline-attribution report (JSON + .md)")
    ap.add_argument("--telemetry-every", type=int, default=0,
                    help="probe cadence for the telemetry twin (0 = off)")
    ap.add_argument("--step-log", default=None,
                    help="bounded per-step JSONL log path")
    ap.add_argument("--calibration", default=None,
                    help="@cal.json artifact for drift baselines "
                         "(default: self-baseline on the first window)")
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    policy = TransPolicy.from_spec(args.policy)
    model = build_model(cfg)
    opt_cfg = AdamWConfig(lr=args.lr, moment_fmt=policy.optimizer)

    pipe = SyntheticLMPipeline(vocab=cfg.vocab, seq_len=args.seq,
                               global_batch=args.batch, seed=args.seed)
    params = model.init(jax.random.key(args.seed))
    opt_state = adamw_init(params, opt_cfg)

    step_kw = dict(warmup=max(args.steps // 10, 1), total_steps=args.steps)
    step_fn_raw = make_train_step(model, policy, opt_cfg, **step_kw)
    jitted = jax.jit(step_fn_raw, donate_argnums=(0, 1))

    # observability sinks (all off by default; DESIGN.md §16)
    telemetry = tracer = jitted_probed = None
    registry = None
    if args.metrics_out or args.telemetry_every:
        from repro.obs.metrics import MetricsRegistry
        registry = MetricsRegistry()
    if args.telemetry_every:
        from repro.obs.train import TrainingTelemetry
        telemetry = TrainingTelemetry(
            policy=policy, baselines=args.calibration,
            every=args.telemetry_every, metrics=registry,
            log_path=args.step_log)
        # the probed twin: telemetry metrics + observer callbacks bake into
        # THIS executable only — the plain step stays callback-free (JP005)
        jitted_probed = jax.jit(
            make_train_step(model, policy, opt_cfg, telemetry=True,
                            **step_kw),
            donate_argnums=(0, 1))
    if args.trace_out:
        from repro.obs.trace import TraceRecorder
        tracer = TraceRecorder()
        tracer.label_track(0, "train steps")

    def make_batch(step):
        b = pipe.batch_at(step)
        if cfg.family == "whisper":
            k = jax.random.fold_in(jax.random.key(args.seed ^ 0xF0), step)
            b["frames"] = jax.random.normal(
                k, (args.batch, cfg.enc_frames, cfg.d_model), jnp.float32)
        if cfg.family == "vlm":
            k = jax.random.fold_in(jax.random.key(args.seed ^ 0xF1), step)
            b["patch_embeds"] = jax.random.normal(
                k, (args.batch, cfg.n_patches, cfg.d_model), jnp.float32)
        return b

    history = []
    wall0 = time.perf_counter()

    def step_fn(state, step):
        p, o = state["params"], state["opt"]
        batch = make_batch(step)
        probed = telemetry is not None and telemetry.should_probe(step)
        t0 = time.perf_counter()
        if probed:
            with telemetry.observing():
                p, o, metrics = jitted_probed(p, o, batch, jnp.asarray(step))
        else:
            p, o, metrics = jitted(p, o, batch, jnp.asarray(step))
        t1 = time.perf_counter()
        if tracer is not None:
            tracer.span("probed_step" if probed else "step",
                        t0 - wall0, t1 - wall0,
                        args={"step": step})
        if telemetry is not None:
            event = telemetry.on_step(step, metrics, step_s=t1 - t0,
                                      probed=probed)
            if probed:
                print(json.dumps({
                    "kind": "train/telemetry", "step": step,
                    "probes": telemetry.watcher.probes,
                    "checks": telemetry.watcher.checks,
                    "recalibrate": telemetry.recalibrate,
                    "quire_saturation": telemetry.quire_saturation(),
                    "update_ratio": float(metrics["update_ratio"]),
                    "grad_nonfinite": int(metrics["grad_nonfinite"]),
                    "opt_nonfinite": int(metrics["opt_nonfinite"]),
                }), flush=True)
            if event is not None:
                if tracer is not None:
                    tracer.instant("drift", t1 - wall0, args=event)
                print(json.dumps({"kind": "train/drift", "step": step,
                                  **event}), flush=True)
        if step % args.log_every == 0 or step == args.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            m["step"] = step
            history.append(m)
            print(json.dumps({"kind": "train/step", **m}), flush=True)
        return {"params": p, "opt": o}

    state = {"params": params, "opt": opt_state}
    try:
        if args.ckpt_dir:
            mgr = CheckpointManager(args.ckpt_dir, keep=2,
                                    fmt=policy.checkpoint)
            loop = FaultTolerantLoop(
                ckpt=mgr, save_every=args.save_every,
                preemption=PreemptionSignal(install_sigterm=True))
            state, start = loop.resume(state)
            if start:
                print(f"[resume] from step {start}", file=sys.stderr)
            t0 = time.perf_counter()
            state, nxt = loop.run(state, step_fn, start_step=start,
                                  num_steps=args.steps - start)
            mgr.wait()
            mgr.close()
            print(json.dumps({"kind": "train/done", "done": nxt,
                              "wall_s": round(time.perf_counter() - t0, 1),
                              **loop.stats}))
        else:
            t0 = time.perf_counter()
            for step in range(args.steps):
                state = step_fn(state, step)
            print(json.dumps({"kind": "train/done", "done": args.steps,
                              "wall_s": round(time.perf_counter() - t0, 1)}))

        if args.profile_out:
            _profile_step(args, step_fn_raw, state, make_batch)
    finally:
        # telemetry flushes in finally: a preempted/crashed run must still
        # leave its step log + metrics snapshot on disk for post-mortem
        if telemetry is not None:
            telemetry.close()
        if registry is not None and args.metrics_out:
            if telemetry is not None:
                registry.set_context(telemetry=telemetry.report())
            registry.set_context(arch=cfg.name, policy=policy.describe(),
                                 steps=args.steps, history=history)
            registry.save(args.metrics_out)
            with open(args.metrics_out + ".prom", "w") as f:
                f.write(registry.prometheus())
        if tracer is not None:
            tracer.save(args.trace_out)
    return state


def _profile_step(args, step_fn_raw, state, make_batch):
    """One eagerly-executed profiled step -> roofline-attribution report.

    Eager (un-jitted) on purpose: every kernel entry point dispatches with
    concrete arrays, so the profiler can time each dispatch; sites inside
    the autodiff trace or scanned layer stacks record as ``traced`` with
    analytic cost only (obs/prof.py).
    """
    from repro.obs import prof

    profiler = prof.KernelProfiler()
    with prof.profiling(profiler):
        t0 = time.perf_counter()
        out = step_fn_raw(state["params"], state["opt"],
                          make_batch(args.steps), jnp.asarray(args.steps))
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
    rep = profiler.save(args.profile_out, measured_total_s=dt)
    print(json.dumps({"kind": "train/profile",
                      "profile_out": args.profile_out,
                      "rows": len(rep["rows"]),
                      "dispatches": rep["totals"]["dispatches"],
                      "bytes": rep["totals"]["bytes"],
                      "bound_s": rep["totals"]["bound_s"],
                      "measured_s": round(dt, 4)}), flush=True)


if __name__ == "__main__":
    use_compile_cache()
    main()
