"""Serving-plane observability tests (DESIGN.md §12).

The load-bearing guarantees (ISSUE 6 acceptance):

* the metrics registry's percentile readout is *bit-identical* to
  ``numpy.percentile`` while the sample buffer is retained, and bounded by
  the log-bucket ratio after the cap drops it,
* the numerics probes stream correct binade histograms from inside
  ``jax.jit`` + ``lax.scan`` (the decode-executable shape), and the
  callbacks bake in at trace time — the probed/plain twin-executable
  mechanism the engine relies on,
* the drift detector fires on a shifted activation distribution and stays
  quiet on in-distribution traffic, end-to-end through a saved calibration
  artifact (``save_artifact -> load_baselines``),
* the engine's metrics agree with its own ``Completion`` records (same
  timestamps, two independent aggregation paths),
* the trace output is schema-valid Chrome trace-event JSON.
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.calib.observe import BIN_LO, NBINS, Observer, TensorStats, observing
from repro.configs import get_arch
from repro.core.pcsr import TransPolicy
from repro.launch.engine import ContinuousBatchingEngine, Request
from repro.models.registry import build_model
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               RollingRate, percentile, percentile_ms)
from repro.obs.numerics import (NumericsWatcher, chi2_quantile, drift_score,
                                drift_threshold, load_baselines,
                                normal_quantile)
from repro.obs.trace import TraceRecorder, annotate, named_scope

#: Quarter-decade bucket ratio: the bucket-interpolated percentile error
#: bound once the exact sample buffer is dropped.
_BUCKET_RATIO = 10.0 ** 0.25


def _drain_callbacks(out) -> None:
    jax.block_until_ready(out)
    jax.effects_barrier()


# ----------------------------------------------------------------- metrics ----

def test_percentile_helpers_match_numpy():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(-3, 2, 257).tolist()
    for q in (0, 12.5, 50, 95, 99, 100):
        assert percentile(xs, q) == float(np.percentile(xs, q))
    assert percentile([], 50) == 0.0
    assert percentile_ms([0.0012344], 50) == 1.23       # rounded ms


def test_histogram_exact_percentiles_are_numpy():
    rng = np.random.default_rng(1)
    xs = rng.lognormal(-4, 2, 500)
    h = Histogram("t")
    for x in xs:
        h.observe(float(x))
    assert h.exact and h.n == 500
    p = h.percentiles((50, 95, 99))
    assert p["p50"] == float(np.percentile(xs, 50))
    assert p["p95"] == float(np.percentile(xs, 95))
    assert p["p99"] == float(np.percentile(xs, 99))
    assert h.min == xs.min() and h.max == xs.max()
    assert h.mean == pytest.approx(xs.mean())
    d = h.to_dict()
    assert d["count"] == 500 and d["exact"] and d["p95"] == p["p95"]


def test_histogram_bucket_fallback_is_ratio_bounded():
    rng = np.random.default_rng(2)
    xs = rng.lognormal(-5, 1.5, 2000)
    h = Histogram("t", max_samples=64)
    for x in xs:
        h.observe(float(x))
    assert not h.exact                      # buffer dropped past the cap
    assert sum(h.counts) == h.n == 2000
    for q in (50, 95, 99):
        est = h.percentiles((q,))[f"p{q:g}"]
        true = float(np.percentile(xs, q))
        assert true / _BUCKET_RATIO <= est <= true * _BUCKET_RATIO, \
            f"p{q}: bucket estimate {est} vs numpy {true}"


def test_histogram_bucket_assignment_matches_searchsorted():
    rng = np.random.default_rng(3)
    xs = rng.lognormal(-4, 3, 300)
    h = Histogram("t")
    for x in xs:
        h.observe(float(x))
    want = np.zeros(len(h.buckets) + 1, np.int64)
    np.add.at(want, np.searchsorted(h.buckets, xs, side="left"), 1)
    assert h.counts == want.tolist()


def test_counter_labels_and_gauge():
    c = Counter("finished")
    c.inc(label="eos")
    c.inc(2, label="max_new")
    c.inc(label="eos")
    assert c.value("eos") == 2 and c.value("max_new") == 2
    assert c.total == 4 and c.value("missing") == 0
    assert c.to_dict()["by_label"] == {"eos": 2.0, "max_new": 2.0}
    plain = Counter("n")
    plain.inc(3)
    assert plain.to_dict() == {"total": 3.0}    # unlabeled: no by_label noise
    g = Gauge("occ")
    g.set(0.75)
    assert g.to_dict() == {"value": 0.75}


def test_rolling_rate_window():
    r = RollingRate(window_s=10.0)
    for t in range(10):
        r.add(float(t), 5.0)                    # 5 tok/s for 10 s
    assert r.rate(10.0) == pytest.approx(5.0, rel=0.15)
    # short run: rate over the covered span, not diluted over the window
    r2 = RollingRate(window_s=10.0)
    r2.add(0.0, 10.0)
    r2.add(2.0, 10.0)
    assert r2.rate(2.0) == pytest.approx(10.0)
    # old events slide out
    assert r.rate(100.0) == 0.0


def test_registry_snapshot_and_save(tmp_path):
    m = MetricsRegistry()
    m.counter("steps").inc(7)
    m.gauge("occ").set(0.5)
    m.histogram("lat").observe(0.25)
    m.set_context(arch="yi-34b", mode="continuous")
    snap = m.snapshot()
    assert snap["kind"] == "repro/metrics-snapshot"
    assert snap["arch"] == "yi-34b"
    assert snap["counters"]["steps"]["total"] == 7
    assert snap["histograms"]["lat"]["count"] == 1
    path = tmp_path / "metrics.json"
    m.save(str(path))
    assert json.loads(path.read_text()) == json.loads(json.dumps(snap))
    # create-on-first-use returns the same instrument
    assert m.counter("steps") is m.counter("steps")


def test_prometheus_exposition():
    m = MetricsRegistry()
    m.counter("requests_finished").inc(label="eos")
    m.counter("requests_finished").inc(2, label="max_new")
    m.gauge("slot_occupancy").set(0.5)
    h = m.histogram("decode_step_s")
    for x in (0.001, 0.002, 0.004, 1.5):
        h.observe(x)
    text = m.prometheus()
    lines = text.splitlines()
    assert 'requests_finished_total{reason="eos"} 1' in lines
    assert 'requests_finished_total{reason="max_new"} 2' in lines
    assert "slot_occupancy 0.5" in lines
    assert 'decode_step_s_bucket{le="+Inf"} 4' in lines
    assert "decode_step_s_count 4" in lines
    # cumulative le buckets are monotone and end at the total count
    cum = [int(ln.rsplit(" ", 1)[1]) for ln in lines
           if ln.startswith("decode_step_s_bucket")]
    assert cum == sorted(cum) and cum[-1] == 4


# ------------------------------------------------------------------- trace ----

def test_trace_recorder_chrome_schema(tmp_path):
    tr = TraceRecorder()
    tr.label_track(0, "engine")
    tr.span("decode_step", 1.0, 2.5, tid=0, args={"emitted": 3})
    tr.instant("evict rid=0", 2.5, tid=1)
    doc = tr.to_json()
    assert isinstance(doc["traceEvents"], list) and len(doc["traceEvents"]) == 3
    meta, span, inst = doc["traceEvents"]
    assert meta["ph"] == "M" and meta["args"]["name"] == "engine"
    assert span["ph"] == "X" and span["ts"] == 1e6 and span["dur"] == 1.5e6
    assert inst["ph"] == "i" and inst["s"] == "t" and inst["ts"] == 2.5e6
    for ev in doc["traceEvents"]:
        assert {"name", "ph", "pid", "tid"} <= set(ev)
    path = tmp_path / "trace.json"
    tr.save(str(path))
    assert json.loads(path.read_text())["otherData"]["dropped_events"] == 0


def test_trace_recorder_bounds_memory():
    tr = TraceRecorder(max_events=3)
    for i in range(10):
        tr.span(f"s{i}", i, i + 1)
    assert len(tr.events) == 3 and tr.dropped == 7
    assert tr.to_json()["otherData"]["dropped_events"] == 7


def test_annotate_and_named_scope_are_harmless():
    with annotate("repro.test"), named_scope("repro.test"):
        assert jnp.add(1, 1) == 2


# ------------------------------------------------ probes under jit + scan ----

def _binade_hist(xs: np.ndarray) -> np.ndarray:
    """Numpy oracle for the observer's binade histogram (finite, nonzero)."""
    xs = np.abs(xs[np.isfinite(xs)].astype(np.float64))
    xs = xs[xs > 0]
    e = np.clip(np.floor(np.log2(xs)).astype(int), BIN_LO, BIN_LO + NBINS - 1)
    hist = np.zeros((NBINS,), np.float64)
    np.add.at(hist, e - BIN_LO, 1)
    return hist


def test_observer_streams_exact_binades_from_jit_scan():
    rng = np.random.default_rng(4)
    xs = rng.lognormal(0, 8, (6, 64)).astype(np.float32)
    xs[0, 0] = 0.0
    xs[1, 2] = np.inf

    from repro.calib import observe as obs_mod

    @jax.jit
    def f(xs):
        def body(carry, x):
            obs_mod.record("scan/site", "act", x)
            return carry + x.sum(), ()
        out, _ = jax.lax.scan(body, 0.0, xs)
        return out

    obs = Observer(kinds=("act",))
    with observing(obs):
        _drain_callbacks(f(jnp.asarray(xs)))
    st = obs.get("scan/site", "act")
    assert st.n == xs.size                      # all scan iterations merged
    assert st.nonfinite == 1
    np.testing.assert_array_equal(st.hist, _binade_hist(xs))

    # trace-time baking: the compiled executable keeps its callbacks — a
    # later call OUTSIDE the observing block still streams (this is what
    # lets the engine wrap only the probed twin's first call)
    n0 = st.n
    _drain_callbacks(f(jnp.asarray(xs)))
    assert obs.get("scan/site", "act").n == 2 * n0


def test_observer_kinds_filter_is_trace_time_dead_code():
    obs = Observer(kinds=("act",))
    with observing(obs):
        _drain_callbacks(jax.jit(
            lambda x: (obs.record("w", "weight", x), x + 1)[1])(jnp.ones(8)))
    assert obs.stats == {}                      # weight never even streamed


# ---------------------------------------------------------- drift detection ----

def test_normal_and_chi2_quantiles():
    assert normal_quantile(0.975) == pytest.approx(1.959964, abs=1e-5)
    assert normal_quantile(0.999) == pytest.approx(3.090232, abs=1e-5)
    assert normal_quantile(0.025) == pytest.approx(-1.959964, abs=1e-5)
    with pytest.raises(ValueError):
        normal_quantile(0.0)
    # Wilson–Hilferty vs scipy.stats.chi2.ppf reference values
    assert chi2_quantile(2, 0.999) == pytest.approx(13.8155, rel=0.05)
    assert chi2_quantile(10, 0.999) == pytest.approx(29.5883, rel=0.02)


def _stats_at(binade: int, n: float = 4096.0, spread: int = 3) -> TensorStats:
    """TensorStats with lognormal-ish mass centered on ``binade``."""
    st = TensorStats()
    weights = [1.0, 4.0, 10.0, 4.0, 1.0][:2 * spread - 1]
    total = sum(weights)
    for off, w in zip(range(-spread + 1, spread), weights):
        st.hist[binade + off - BIN_LO] = n * w / total
    st.n = n
    return st


def test_drift_score_quiet_then_fires():
    base = _stats_at(0, n=65536)
    live_same = _stats_at(0, n=8192)
    live_shift = _stats_at(6, n=8192)           # six binades over: drifted
    s0, k0 = drift_score(live_same, base)
    s1, k1 = drift_score(live_shift, base)
    t0 = drift_threshold(8192, 65536, k0)
    t1 = drift_threshold(8192, 65536, k1)
    assert s0 < t0, "identical distribution must stay under threshold"
    assert s1 > t1, "shifted distribution must exceed threshold"
    assert s1 > s0 and k1 > k0                  # disjoint support widens k


def test_drift_threshold_floor_and_degenerate():
    # plentiful samples: the chi2 term shrinks below min_score and the floor
    # takes over (non-iid activations — see numerics.py docstring)
    assert drift_threshold(1e6, 1e6, 5, min_score=0.1) == 0.1
    # scarce samples: the calibrated chi2 term dominates the floor
    assert drift_threshold(20, 20, 5, min_score=0.1) > 0.1
    assert drift_threshold(0, 100, 5) == math.inf
    assert drift_threshold(100, 100, 1) == math.inf
    empty = TensorStats()
    assert drift_score(empty, _stats_at(0)) == (0.0, 0)


def test_watcher_saturation_underflow_rates():
    pol = TransPolicy.from_names(weights="p8_0")
    ms = pol.weights.max_scale
    w = NumericsWatcher(policy=pol, every=1)
    st = TensorStats()
    st.hist[0 - BIN_LO] = 80                    # in-range mass
    st.hist[ms - BIN_LO] = 15                   # at max_scale: clamps to maxpos
    st.hist[-ms - 1 - BIN_LO] = 5               # below -max_scale: minpos
    st.n = 102.0
    st.nonfinite = 2.0
    w.observer.stats[("blocks/mlp/up", "act")] = st
    health = w.check()
    h = health["blocks/mlp/up"]
    assert h.saturation_rate == pytest.approx(0.15)
    assert h.underflow_rate == pytest.approx(0.05)
    assert h.nonfinite == 2.0
    assert h.drift_score is None                # no baseline for this site
    assert not h.drifted and not w.recalibrate


def test_watcher_cadence_rebase_and_latch():
    with pytest.raises(ValueError, match="cadence"):
        NumericsWatcher(every=0)
    w = NumericsWatcher(every=8)
    assert [w.should_probe(i) for i in (0, 1, 7, 8, 16)] == \
        [True, False, False, True, True]

    base = _stats_at(0, n=65536)
    w = NumericsWatcher(baselines={"s": base}, every=1)
    w.observer.stats[("s", "act")] = _stats_at(0, n=1024)
    # rebase: warmup traffic is marked off, the first window starts empty
    w.rebase()
    assert w.check() == {}
    # window 1: drifted traffic -> flag raises
    st = w.observer.stats[("s", "act")]
    shifted = _stats_at(8, n=1024)
    st.hist += shifted.hist
    st.n += shifted.n
    h1 = w.check()
    assert h1["s"].drifted and w.recalibrate
    # window 2: back in distribution -> window health clears but the flag
    # LATCHES (the operator must recalibrate, not wait it out)
    ok = _stats_at(0, n=1024)
    st.hist += ok.hist
    st.n += ok.n
    h2 = w.check()
    assert not h2["s"].drifted
    assert w.recalibrate
    rep = w.report()
    assert rep["recalibrate"] and rep["probe_every"] == 1
    assert rep["sites"]["s"]["drifted"] is False


# ------------------------------------------------- drift e2e via artifact ----

@pytest.fixture(scope="module")
def small_model():
    cfg = get_arch("phi3-mini-3.8b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def test_drift_detector_end_to_end(small_model, tmp_path):
    """Calibrate -> save artifact -> load baselines -> serve-time forward:
    in-distribution traffic stays quiet, a scaled parameter set (activation
    distribution shifted by several binades) raises recalibrate."""
    from repro.calib.search import calibrate_model, save_artifact

    cfg, model, params = small_model
    rng = np.random.default_rng(0)
    base = TransPolicy()

    def batch():
        return {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 32))),
                "labels": jnp.asarray(rng.integers(0, cfg.vocab, (2, 32)))}

    pol, report = calibrate_model(
        lambda b: model.loss(params, b, base)[0], [batch(), batch()],
        params, base=base, name="t")
    path = tmp_path / "cal.json"
    save_artifact(str(path), pol, report)
    baselines = load_baselines(str(path))
    assert baselines and all(st.n > 0 for st in baselines.values())
    assert "mlp/up" in baselines or "mlp/gate" in baselines

    def probe_forward(p):
        w = NumericsWatcher(policy=pol, baselines=baselines, every=1)
        with w.observing():
            _drain_callbacks(model.forward(p, batch(), base))
        w.check()
        return w

    # in-distribution: same params, fresh batch from the same token prior
    quiet = probe_forward(params)
    scored = [h for h in quiet.health.values() if h.drift_score is not None]
    assert scored, "baselines must cover observed sites"
    assert not quiet.recalibrate, \
        {h.path: h.drift_score for h in scored if h.drifted}

    # shifted: scaling every weight moves activation binades layer by layer
    loud = probe_forward(jax.tree.map(lambda x: x * 2.0 ** 6, params))
    assert loud.recalibrate
    assert loud.report()["max_drift_score"] > quiet.report()["max_drift_score"]


# -------------------------------------------------------- engine integration ----

@pytest.fixture(scope="module")
def observed_run():
    """One deterministic engine run with all three sinks attached."""
    cfg = get_arch("yi-34b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    policy = TransPolicy.from_names(kv_cache="p8_0", attn_impl="kernel")
    metrics, tracer = MetricsRegistry(), TraceRecorder()
    # the watcher's policy only interprets formats (saturation thresholds);
    # weights stay unquantized in the serving policy above
    numerics = NumericsWatcher(
        policy=TransPolicy.from_names(weights="p8_0"), every=4)
    eng = ContinuousBatchingEngine(
        model, params, policy, max_slots=2, S_max=64,
        metrics=metrics, tracer=tracer, numerics=numerics)
    rng = np.random.default_rng(0)
    for rid, (plen, arr) in enumerate([(12, 0.0), (7, 0.0), (9, 1.0)]):
        eng.submit(Request(
            rid=rid, prompt=rng.integers(0, cfg.vocab, (plen,)).astype(np.int32),
            max_new_tokens=5, arrival_time=arr))
    # deterministic clock: admission at t=2, each decode step one tick later
    eng.admit(now=2.0)
    t = 3.0
    while eng.active.any() or eng.queue:
        if eng.queue and eng.free_slots():
            eng.admit(now=t)
        eng.step(now=t)
        t += 1.0
    return eng, metrics, tracer, numerics


def test_engine_metrics_match_completions(observed_run):
    eng, m, _, _ = observed_run
    comps = eng.completions
    assert len(comps) == 3 and all(c.finish_reason == "max_new" for c in comps)
    assert m.counter("requests_admitted").total == 3
    assert m.counter("requests_finished").value("max_new") == 3
    assert m.counter("decode_steps").total == eng.steps
    assert m.counter("tokens_emitted").total == sum(len(c.tokens) for c in comps)
    # the histograms retained every sample: compare against the Completion
    # records, which were stamped from the same deterministic clock
    for name, want in [
        ("queue_s", [c.queue_s for c in comps]),
        ("ttft_s", [c.ttft_s for c in comps]),
        ("request_s", [c.finished_time - c.admitted_time for c in comps]),
        ("inter_token_s", [dt for c in comps for dt in c.per_token_s()[1:]]),
    ]:
        h = m.histograms[name]
        assert h.exact
        assert sorted(h._samples) == pytest.approx(sorted(want)), name
    assert m.gauge("slot_occupancy").val == 0.0        # drained
    assert m.gauge("queue_depth").val == 0.0
    assert m.histograms["slots_active"].max <= eng.max_slots
    snap = m.snapshot()
    assert snap["histograms"]["inter_token_s"]["count"] == \
        sum(len(c.tokens) - 1 for c in comps)


def test_engine_probes_and_recalibrate_gauge(observed_run):
    eng, m, _, numerics = observed_run
    # cadence 4 with step 0 included: ceil(steps / 4) probed steps
    assert numerics.probes == -(-eng.steps // 4)
    rep = numerics.report()
    assert rep["sites"], "probed steps must populate per-site health"
    assert not rep["recalibrate"]               # no baselines -> never drifts
    assert m.gauge("numerics_recalibrate").val == 0.0
    for h in rep["sites"].values():
        assert h["n"] > 0 and h["nonfinite"] == 0
        assert 0.0 <= h["saturation_rate"] <= 1.0


def test_engine_trace_spans(observed_run):
    eng, _, tracer, _ = observed_run
    doc = tracer.to_json()
    names = [ev["name"] for ev in doc["traceEvents"]]
    assert "engine" in [ev["args"]["name"] for ev in doc["traceEvents"]
                        if ev["ph"] == "M"]
    for rid in (0, 1, 2):
        assert f"queued rid={rid}" in names
        assert f"prefill rid={rid}" in names
        assert f"decode rid={rid}" in names
    assert names.count("decode_step") == eng.steps
    # request lifecycle rides the slot track; the engine track is tid 0
    decode_tids = {ev["tid"] for ev in doc["traceEvents"]
                   if ev["name"] == "decode_step"}
    assert decode_tids == {0}
    evicts = [ev for ev in doc["traceEvents"] if ev["name"].startswith("evict")]
    assert len(evicts) == 3 and all(ev["ph"] == "i" for ev in evicts)
    json.dumps(doc)                             # serializable as-is


def test_engine_cancel_paths():
    cfg = get_arch("yi-34b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    policy = TransPolicy.from_names(kv_cache="p8_0")
    m = MetricsRegistry()
    eng = ContinuousBatchingEngine(model, params, policy, max_slots=1,
                                   S_max=64, metrics=m)
    rng = np.random.default_rng(5)
    for rid in range(3):
        eng.submit(Request(
            rid=rid, prompt=rng.integers(0, cfg.vocab, (8,)).astype(np.int32),
            max_new_tokens=20))
    eng.admit(now=1.0)
    eng.step(now=2.0)
    # mid-flight: evicted with partial tokens, reason recorded
    assert eng.cancel(0, now=3.0)
    assert eng.completions[0].finish_reason == "cancel"
    assert len(eng.completions[0].tokens) == 2  # prefill token + one step
    assert m.counter("requests_finished").value("cancel") == 1
    # queued: dropped without a Completion
    assert eng.cancel(2)
    assert m.counter("requests_cancelled_queued").total == 1
    assert [r.rid for r in eng.queue] == [1]
    # unknown rid
    assert not eng.cancel(99)
    # the freed slot serves the remaining request to completion
    eng.admit(now=4.0)
    while eng.active.any():
        eng.step(now=5.0)
    assert {c.rid for c in eng.completions} == {0, 1}
