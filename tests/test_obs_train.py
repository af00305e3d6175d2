"""Training-plane observability (DESIGN.md §16): gradient observer, kernel
profiler, drift latch, step log.

* the ``grad_tap`` cotangent hook is a bit-exact identity (gradients with
  and without the tap are equal bitwise) whose recorded stats match a numpy
  oracle, and it records exactly once per step under ``jit`` + ``lax.scan``
  + ``jax.checkpoint`` rematerialization,
* the profiler's analytic bytes/FLOPs agree with the ``launch/roofline.py``
  closed forms computed by hand for the GEMM and attention families (the
  ISSUE acceptance bar), and eager vs traced dispatches are kept apart,
* the drift latch fires when a mid-run parameter scaling shifts a site's
  activation distribution away from its self-baseline,
* ``JsonlStepLog`` bounds its record count and ``TrainingTelemetry`` drains
  device scalars into gauges/log off the step path.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.calib import observe
from repro.calib.observe import Observer, observing
from repro.core import (
    OperandSlots, P8_0, P16_1, TransPolicy, posit_encode,
)
from repro.kernels.posit_attention import ops as attn_ops
from repro.kernels.posit_gemm import ops as gemm_ops
from repro.launch import roofline
from repro.models.layers import apply_linear, init_linear
from repro.obs import prof
from repro.obs.train import JsonlStepLog, TrainingTelemetry


def _drain_callbacks():
    """debug.callback effects are asynchronous; drain before reading stats."""
    jax.effects_barrier()


# ------------------------------------------------------------- grad observer --

def test_grad_tap_identity_and_numpy_oracle():
    """The tap never perturbs the computation (bitwise-identical gradients)
    and the recorded cotangent stats match the hand-derived numpy grad."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (4, 8)).astype(np.float32))
    W = jnp.asarray(rng.normal(0, 1, (8, 6)).astype(np.float32))

    def loss_plain(x):
        return jnp.sum(jnp.tanh(x @ W))

    def loss_tapped(x):
        return jnp.sum(jnp.tanh(observe.grad_tap("site", x) @ W))

    g_plain = jax.jit(jax.grad(loss_plain))(x)
    obs = Observer(kinds=("act", "grad"))
    with observing(obs):
        g_tapped = jax.jit(jax.grad(loss_tapped))(x)
        jax.block_until_ready(g_tapped)
    _drain_callbacks()
    assert np.array_equal(np.asarray(g_plain), np.asarray(g_tapped))

    # numpy oracle for the cotangent arriving at the tap: dL/dx
    xn, Wn = np.asarray(x, np.float64), np.asarray(W, np.float64)
    g_ref = (1.0 - np.tanh(xn @ Wn) ** 2) @ Wn.T
    st = obs.stats[("site", "grad")]
    assert st.n == x.size and st.nonfinite == 0
    np.testing.assert_allclose(st.sum_sq, np.sum(g_ref ** 2), rtol=1e-5)
    np.testing.assert_allclose(st.abs_max, np.abs(g_ref).max(), rtol=1e-6)


def test_grad_tap_records_once_under_scan_and_checkpoint():
    """``jax.checkpoint`` replays the *forward* during the backward pass; the
    custom_vjp bwd must still run exactly once per scan iteration, or every
    histogram count doubles and drift scoring is silently biased."""
    W = jnp.eye(8, dtype=jnp.float32) * 0.5
    x = jnp.ones((1, 8), jnp.float32)

    def body(h, _):
        return jnp.tanh(observe.grad_tap("s", h) @ W), None

    def loss(x):
        run = jax.checkpoint(
            lambda h: jax.lax.scan(body, h, None, length=3)[0])
        return jnp.sum(run(x))

    obs = Observer(kinds=("act", "grad"))
    with observing(obs):
        jax.block_until_ready(jax.jit(jax.grad(loss))(x))
    _drain_callbacks()
    st = obs.stats[("s", "grad")]
    assert st.n == 3 * x.size, st.n


def test_grad_tap_is_noop_without_grad_kind():
    """Calibration's default observer must not gain tap overhead: with no
    "grad" channel armed the tap is the identity function itself."""
    x = jnp.ones((2, 2))
    obs = Observer()                    # default: ("weight", "act")
    with observing(obs):
        assert observe.grad_tap("p", x) is x
    assert observe.grad_tap("p", x) is x   # and outside any context


# ---------------------------------------------------------- kernel profiler ---

def test_profiler_gemm_bytes_match_roofline_hand_formula():
    M, K, N = 4, 8, 16
    rng = np.random.default_rng(1)
    a = posit_encode(jnp.asarray(rng.normal(0, 1, (M, K)), jnp.float32), 8, 0)
    b = posit_encode(jnp.asarray(rng.normal(0, 1, (K, N)), jnp.float32), 8, 0)
    slots = OperandSlots(rs1=P8_0, rs2=P8_0, rd=P16_1)

    profiler = prof.KernelProfiler()
    with prof.profiling(profiler), prof.site("blk/up"):
        gemm_ops.gemm(a, b, slots, impl="xla")
    (rec,) = [r for r in profiler.records.values() if r.family == "gemm"]

    # hand formula (DESIGN.md §6/§16): 2MKN FLOPs; A and B move at code
    # width (1 byte for p8), the output at its storage width (2 for p16)
    assert rec.flops == 2 * M * K * N
    assert rec.bytes == M * K * 1 + K * N * 1 + M * N * 2
    ref = roofline.gemm_cost(M, K, N, a_bytes=1, b_bytes=1, out_bytes=2)
    assert rec.flops == ref["flops"] and rec.bytes == ref["bytes"]
    assert rec.path == "blk/up" and rec.calls == 1 and rec.traced == 0
    assert rec.seconds > 0


def test_profiler_attention_bytes_match_roofline_hand_formula():
    B, Hq, Hkv, S, d = 2, 4, 2, 64, 16
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(0, 1, (B, Hq, d)), jnp.float32)
    kc = posit_encode(
        jnp.asarray(rng.normal(0, 1, (B, Hkv, S, d)), jnp.float32), 8, 0)
    vc = posit_encode(
        jnp.asarray(rng.normal(0, 1, (B, Hkv, S, d)), jnp.float32), 8, 0)
    lengths = jnp.full((B,), S, jnp.int32)

    profiler = prof.KernelProfiler()
    with prof.profiling(profiler):
        attn_ops.decode_attention(q, kc, vc, lengths, 0, kv_bits=8,
                                  impl="tiled")
    (rec,) = [r for r in profiler.records.values()
              if r.family == "attention"]

    # hand formula: QK^T + AV = 4*B*Hq*S*d FLOPs; K and V stream once at
    # code width over the allocated S, q and the output move at f32
    assert rec.flops == 4 * B * Hq * S * d
    assert rec.bytes == B * Hq * d * (4 + 4) + 2 * B * Hkv * S * d * 1
    ref = roofline.attention_decode_cost(B, Hq, Hkv, S, d, kv_bytes=1)
    assert rec.flops == ref["flops"] and rec.bytes == ref["bytes"]


def test_profiler_traced_vs_eager_dispatch():
    """Dispatches under a jit trace count as ``traced`` (once per compile,
    never timed); eager dispatches are counted and timed."""
    x = jnp.ones((2, 8), jnp.float32)
    p = init_linear(jax.random.PRNGKey(0), 8, 4)
    policy = TransPolicy.from_names()

    profiler = prof.KernelProfiler()
    with prof.profiling(profiler):
        jax.jit(lambda p, x: apply_linear(p, x, policy, path="l"))(p, x)
        apply_linear(p, x, policy, path="l")
    rec = profiler.records[("l", "gemm", "xla")]
    assert rec.traced == 1 and rec.calls == 1
    rep = profiler.report(measured_total_s=1.0)
    assert rep["totals"]["dispatches"] == 2
    # the CPU has no entry in the peaks table: no roofline share, never v5e's
    assert rep["peaks"] is None
    assert rep["rows"][0]["bound"] is None
    assert rep["totals"]["achieved_frac"] is None
    assert "not measured" in profiler.markdown(measured_total_s=1.0)


def test_profiler_roofline_uses_running_device_peaks(monkeypatch):
    """On a device in the peaks table the profiler reports its bound and
    floor against that device's peaks."""
    class V5e:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [V5e()])
    x = jnp.ones((2, 8), jnp.float32)
    p = init_linear(jax.random.PRNGKey(0), 8, 4)
    profiler = prof.KernelProfiler()
    with prof.profiling(profiler):
        apply_linear(p, x, TransPolicy.from_names(), path="l")
    rep = profiler.report(measured_total_s=1.0)
    (row,) = rep["rows"]
    assert rep["peaks"] == roofline.PEAKS["TPU v5 lite"]
    assert row["bound"] in ("compute", "memory")
    want = roofline.bound_times(row["flops"], row["bytes"],
                                peaks=roofline.PEAKS["TPU v5 lite"])
    assert row["bound_s"] == want["bound_s"]
    assert rep["totals"]["achieved_frac"] == want["bound_s"] / 1.0


def test_profiler_inactive_is_invisible():
    assert not prof.is_active()
    x = jnp.ones((2, 4), jnp.float32)
    p = init_linear(jax.random.PRNGKey(1), 4, 4)
    y = apply_linear(p, x, TransPolicy.from_names(), path="l")
    assert y.shape == (2, 4)


# ---------------------------------------------------------------- drift latch --

def test_drift_latch_fires_on_midrun_param_scale():
    """Two chained linears under a probed-twin-style telemetry loop: scaling
    the first layer's weights mid-run shifts the second site's activation
    binades off its self-baseline and must latch ``recalibrate``."""
    policy = TransPolicy.from_names()
    tel = TrainingTelemetry(policy=policy, every=1, check_every=1)
    rng = np.random.default_rng(3)
    p1 = init_linear(jax.random.PRNGKey(0), 16, 16)
    p2 = init_linear(jax.random.PRNGKey(1), 16, 8)

    def probed_step(step, p1):
        x = jnp.asarray(rng.normal(0, 1, (8, 16)), jnp.float32)
        with tel.observing():
            h = apply_linear(p1, x, policy, path="l1")
            y = apply_linear(p2, h, policy, path="l2")
        jax.block_until_ready(y)
        _drain_callbacks()
        return tel.on_step(step, {"loss": jnp.sum(y)}, probed=True)

    events = [probed_step(s, p1) for s in range(2)]
    assert events == [None, None] and not tel.recalibrate

    p1_scaled = {k: v * 2.0 ** 8 for k, v in p1.items()}
    events = [probed_step(2 + s, p1_scaled) for s in range(2)]
    fired = [e for e in events if e is not None]
    assert fired, "drift never latched after the mid-run param scale"
    assert fired[0]["recalibrate"] and "l2" in fired[0]["drifted"]
    assert tel.recalibrate
    assert tel.metrics.gauge("train_recalibrate").val == 1.0
    rep = tel.report()
    assert rep["numerics"]["recalibrate"]


# ------------------------------------------------------- step log / telemetry --

def test_jsonl_step_log_bounded(tmp_path):
    path = str(tmp_path / "steps.jsonl")
    log = JsonlStepLog(path, max_records=4)
    for i in range(6):
        log.append({"step": i})
    log.close()
    recs = [json.loads(l) for l in open(path)]
    assert [r["step"] for r in recs] == [0, 1, 2, 3]
    assert log.stats() == {"path": path, "records": 4, "dropped": 2,
                           "max_records": 4}


def test_telemetry_drains_off_step_path(tmp_path):
    """Un-probed steps only buffer (no host sync, no file I/O); the probe
    boundary drains everything pending into the log and gauges."""
    path = str(tmp_path / "steps.jsonl")
    tel = TrainingTelemetry(every=4, check_every=2, log_path=path)
    for step in range(3):
        assert tel.on_step(step, {"loss": jnp.float32(step)}) is None
    assert len(tel._pending) == 3 and tel.log.written == 0

    with tel.observing():
        pass    # a probe with no sites recorded is still a probe
    tel.on_step(3,{"loss": jnp.float32(3.0), "gnorm": jnp.float32(2.0),
                    "update_ratio": jnp.float32(0.5),
                    "grad_nonfinite": jnp.int32(0),
                    "opt_nonfinite": jnp.int32(1)}, probed=True)
    assert tel._pending == [] and tel.log.written == 4
    assert tel.metrics.gauge("train_loss").val == 3.0
    assert tel.metrics.gauge("train_update_ratio").val == 0.5
    tel.close()
    recs = [json.loads(l) for l in open(path)]
    assert len(recs) == 4 and recs[3]["opt_nonfinite"] == 1
    rep = tel.report()
    assert rep["steps"] == 4 and rep["log"]["records"] == 4
