"""Training-telemetry overhead gate: step throughput with the §16 stack on.

The training-plane observability (probed-twin gradient/activation telemetry,
step-health JSONL log, metrics registry — DESIGN.md §16) is only deployable
if the *plain* steps stay free and the probe cost amortizes away at the
default cadence.  This benchmark runs the same train step two ways —
telemetry OFF (the bare jitted step) vs fully ON (a ``TrainingTelemetry`` at
its default cadence, routing every ``every``-th step through the probed twin
and draining the JSONL log at probe boundaries) — with the paired-interleaved
min-statistic construction (bench_obs_overhead / DESIGN.md §8: each round
times both configurations back-to-back, rotating who runs first;
min-over-rounds discards loaded samples), and **asserts** the instrumented
loop stays within ``MAX_OVERHEAD`` (5%) of the bare loop.

One timing round spans exactly one probe cadence cycle (``telemetry.every``
steps), so every round pays exactly one probed-twin step plus one drain —
the steady-state amortized cost, never a lucky probe-free window.

The instrumented run's artifacts are written to the cwd for CI upload:
``train_metrics.json`` (registry snapshot + telemetry report) and
``train_profile.json``/``.md`` (the per-kernel roofline-attribution report
from tracing the step under the §16 profiler).
"""
from __future__ import annotations

import os
import tempfile
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.configs import get_arch
from repro.data.pipeline import SyntheticLMPipeline
from repro.core.pcsr import TransPolicy
from repro.launch.steps import make_train_step
from repro.models.registry import build_model
from repro.obs import prof
from repro.obs.train import TrainingTelemetry
from repro.optim import AdamWConfig, adamw_init

#: Acceptance ceiling: the telemetry-on loop may cost at most this much more
#: than the bare loop at the default probe cadence.
MAX_OVERHEAD = 0.05


def run(smoke: bool = False) -> None:
    rounds = 2 if smoke else 4
    cfg = get_arch("xlstm-125m").reduced()
    policy = TransPolicy.from_spec("p16-train")
    model = build_model(cfg)
    opt_cfg = AdamWConfig(lr=1e-3, moment_fmt=policy.optimizer)
    params = model.init(jax.random.key(0))
    pipe = SyntheticLMPipeline(vocab=cfg.vocab, seq_len=16, global_batch=2,
                               seed=0)
    batch = pipe.batch_at(0)   # fixed batch: host-side generation stays
    #                            out of the timed window for both configs

    step_kw = dict(warmup=1, total_steps=10_000)
    step_fn_raw = make_train_step(model, policy, opt_cfg, **step_kw)
    jitted = jax.jit(step_fn_raw)
    jitted_probed = jax.jit(
        make_train_step(model, policy, opt_cfg, telemetry=True, **step_kw))

    log_path = os.path.join(tempfile.mkdtemp(prefix="bench_train_obs_"),
                            "steps.jsonl")
    telemetry = TrainingTelemetry(policy=policy, log_path=log_path)
    steps = telemetry.every    # one round == one full probe cadence cycle

    def loop_off(state, base, n):
        p, o = state
        for i in range(n):
            p, o, _ = jitted(p, o, batch, jnp.asarray(base + i))
        jax.block_until_ready((p, o))
        return p, o

    def loop_on(state, base, n):
        p, o = state
        for i in range(n):
            step = base + i
            if telemetry.should_probe(step):
                with telemetry.observing():
                    p, o, m = jitted_probed(p, o, batch, jnp.asarray(step))
            else:
                p, o, m = jitted(p, o, batch, jnp.asarray(step))
            telemetry.on_step(step, m, probed=telemetry.should_probe(step))
        jax.block_until_ready((p, o))
        return p, o

    opt = adamw_init(params, opt_cfg)
    loops = {"off": loop_off, "on": loop_on}
    # independent param/opt states per config so both see identical update
    # trajectories; warm both executables (plain + probed twin) off-clock
    states = {n: (params, opt) for n in loops}
    clock = {n: 0 for n in loops}
    for name, fn in loops.items():
        states[name] = fn(states[name], clock[name], 2)
        clock[name] += 2
    with telemetry.observing():
        jax.block_until_ready(
            jitted_probed(*states["on"], batch, jnp.asarray(clock["on"])))

    best = {n: float("inf") for n in loops}
    order = list(loops)
    for r in range(rounds):
        # rotate who runs first: the first-timed loop in a round sees cold
        # caches, and a fixed order would book that cost to one configuration
        for name in order[r % len(order):] + order[:r % len(order)]:
            # align the "on" loop to the cadence so the round pays exactly
            # one probed step wherever the warmup left the counter
            base = clock[name]
            if name == "on":
                base = ((base + steps - 1) // steps) * steps
            t0 = time.perf_counter()
            states[name] = loops[name](states[name], base, steps)
            best[name] = min(best[name],
                             (time.perf_counter() - t0) / steps * 1e6)
            clock[name] = base + steps

    overhead = best["on"] / best["off"] - 1.0
    emit("train_step_plain", best["off"],
         f"steps_per_s={1e6 / best['off']:.2f}")
    emit("train_step_telemetry", best["on"],
         f"steps_per_s={1e6 / best['on']:.2f} "
         f"overhead={overhead * 100:+.2f}% "
         f"probes={telemetry.watcher.probes} every={steps}")

    # the uploaded artifacts: metrics snapshot + roofline attribution
    telemetry.close()
    telemetry.metrics.set_context(arch=cfg.name, bench="train_obs_overhead",
                                  telemetry=telemetry.report())
    telemetry.metrics.save("train_metrics.json")
    # tracing (not running) the step under the profiler yields the analytic
    # attribution report; the jaxpr caches must be dropped first or the
    # warmed inner jits skip their Python bodies and nothing records
    jax.clear_caches()
    profiler = prof.KernelProfiler()
    with prof.profiling(profiler):
        jax.make_jaxpr(step_fn_raw)(params, opt, batch, jnp.asarray(0))
    profiler.save("train_profile.json")

    assert telemetry.watcher.probes > 0, "no probed step ran"
    with open(log_path) as f:
        n_recs = sum(1 for _ in f)
    assert n_recs == telemetry.steps, (
        f"JSONL step log lost records ({n_recs} != {telemetry.steps})")
    assert profiler.records, "profiler recorded no kernel dispatches"
    assert overhead <= MAX_OVERHEAD, (
        f"training-telemetry overhead {overhead:.1%} exceeds the "
        f"{MAX_OVERHEAD:.0%} gate (off={best['off']:.0f}us "
        f"on={best['on']:.0f}us per step)")


if __name__ == "__main__":
    run(smoke=True)
