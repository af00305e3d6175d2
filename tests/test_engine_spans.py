"""Profiler spans of the serving engine (obs/trace.py's span vocabulary).

A device trace can only say why the chip sat idle between two decode steps
if the host's work there is labelled.  These tests run a small paged engine
under ``jax.profiler`` on the CPU, reduce the trace with the benchmark's own
``bench/trace_reduce.load`` and check that:

* every decode step opens one ``repro.engine.step`` holding one each of
  ``repro.decode_step``, ``repro.step.wait``, ``repro.step.emit`` and
  ``repro.step.observe``, and its children cover it;
* every admission opens one ``repro.admit`` holding its ``repro.prefill``;
* the server's drive thread labels its client-op drain;
* tracing changes no token;
* ``bench/metrics/step_host_ms.py`` reads the spans as documented.

Also here: the jitted programs carry stable names (``jit_decode_step``,
``jit_prefill``), and the paged engine's ``kv_utilization`` gauge counts
pool blocks.
"""
import dataclasses
import importlib.util
import pathlib
import sys
import types

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.pcsr import TransPolicy
from repro.launch.engine import ContinuousBatchingEngine, Request
from repro.launch.paged_engine import PagedContinuousBatchingEngine
from repro.launch.server import EngineDriver
from repro.models.registry import build_model
from repro.obs.metrics import MetricsRegistry
from repro.obs.numerics import NumericsWatcher

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

STEP_CHILDREN = ("repro.decode_step", "repro.step.wait", "repro.step.emit",
                 "repro.step.observe")


@pytest.fixture(scope="module")
def setup():
    # eight layers of width 256: a CPU step of some 5 ms, so that a pause of
    # the interpreter between two spans stays small beside it
    cfg = dataclasses.replace(get_arch("yi-34b").reduced(), n_layers=8,
                              d_model=256, d_ff=512)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    policy = TransPolicy.from_names(kv_cache="p8_0", compute_dtype="bf16",
                                    attn_impl="kernel")
    return cfg, model, params, policy


def _paged(setup, **kw):
    _, model, params, policy = setup
    return PagedContinuousBatchingEngine(model, params, policy, max_slots=4,
                                         S_max=64, page_bytes=2048,
                                         n_blocks=24, **kw)


def _requests(cfg, lens, max_new=6):
    rng = np.random.default_rng(3)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n)
                    .astype(np.int32), max_new_tokens=max_new)
            for i, n in enumerate(lens)]


def _serve(eng, reqs) -> tuple:
    """Submit ``reqs``, admit them as slots free and decode to the end;
    returns (tokens by rid, admissions, decode steps)."""
    steps = admitted = 0
    for r in reqs:
        eng.submit(r)
    while eng.queue or eng.active.any():
        if eng.queue and eng.free_slots():
            admitted += eng.admit()
        if eng.active.any():
            steps += eng.step() > 0
    return {c.rid: list(c.tokens) for c in eng.completions}, admitted, steps


def _profile(tmp, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, trace_reduce.load(str(tmp))


@pytest.fixture(scope="module")
def traced_run(setup, tmp_path_factory):
    """One paged engine: a warm-up run (compiles), a traced run, an
    untraced run of the same requests."""
    cfg = setup[0]
    lens = (10, 21, 17, 12, 30)          # five admissions on four slots
    eng = _paged(setup)
    _serve(eng, _requests(cfg, lens))
    eng.reset()
    (toks_on, admitted, steps), tr = _profile(
        tmp_path_factory.mktemp("prof"),
        lambda: _serve(eng, _requests(cfg, lens)))
    eng.reset()
    toks_off, _, _ = _serve(eng, _requests(cfg, lens))
    return tr, toks_on, toks_off, admitted, steps


def _named(tr, name):
    return [h for h in tr.host if h.name == name]


def _inside(parent, evs):
    return [h for h in evs if parent.start <= h.start and h.end <= parent.end
            and h.depth == parent.depth + 1]


def test_each_decode_step_holds_its_phase_spans(traced_run):
    tr, _, _, _, steps = traced_run
    outer = _named(tr, "repro.engine.step")
    assert len(outer) == steps > 0
    for name in STEP_CHILDREN:
        assert len(_named(tr, name)) == steps, name
    for st in outer:
        kids = _inside(st, tr.host)
        for name in STEP_CHILDREN:
            assert [k.name for k in kids].count(name) == 1, (name, kids)


def test_phase_spans_cover_each_step(traced_run):
    tr = traced_run[0]
    for st in _named(tr, "repro.engine.step"):
        covered = sum(k.end - k.start for k in _inside(st, tr.host))
        assert covered >= 0.9 * (st.end - st.start), (covered, st)


def test_each_admission_holds_its_prefill(traced_run):
    tr, _, _, admitted, _ = traced_run
    admits = _named(tr, "repro.admit")
    assert len(admits) == admitted == 5
    for a in admits:
        kids = [k.name for k in _inside(a, tr.host)]
        assert kids.count("repro.prefill") == 1, kids
        assert "repro.admit.kv_scatter" in kids and \
            "repro.admit.wait" in kids, kids
    assert len(_named(tr, "repro.prefill")) == admitted


def test_tracing_changes_no_token(traced_run):
    _, toks_on, toks_off, _, _ = traced_run
    assert len(toks_on) == 5 and toks_on == toks_off


def test_drive_thread_labels_its_op_drain(setup, tmp_path):
    """Spans opened on the server's drive thread reach the trace: the op
    drain each pass, and the engine's own spans beside it."""
    cfg = setup[0]
    eng = _paged(setup)
    _serve(eng, _requests(cfg, (10, 12)))       # compile outside the trace
    eng.reset()
    drv = EngineDriver(eng, max_queue=8)
    drv.start()

    def serve():
        subs = [drv.submit(r.prompt, 4, None).result(timeout=60)[1]
                for r in _requests(cfg, (10, 12))]
        for q in subs:
            while q.get(timeout=60)["event"] != "finish":
                pass

    try:
        _, tr = _profile(tmp_path, serve)
    finally:
        drv.stop()
    assert not drv._thread.is_alive()
    ops = _named(tr, "repro.drive.ops")
    assert ops and all(h.depth == 0 for h in ops)
    assert len(_named(tr, "repro.admit")) == 2
    assert _named(tr, "repro.engine.step")


# ------------------------------------------------------ step_host_ms reader --

def _reader():
    spec = importlib.util.spec_from_file_location(
        "metric_step_host_ms", BENCH / "metrics" / "step_host_ms.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ctx(host, window=(0.0, 1.0)):
    tr = trace_reduce.Trace(ops=[], modules=[], host=host, n_devices=1)
    return types.SimpleNamespace(trace=tr, trace_window=window)


def _steps(starts, wait):
    """A decode-step span and its wait span at each start (seconds)."""
    E = trace_reduce.Ev
    host = []
    for s in starts:
        host += [E(s, s + 0.001, "repro.decode_step", depth=1),
                 E(s + 0.002, s + 0.002 + wait, "repro.step.wait", depth=1)]
    return host


def test_step_host_ms_reads_cycle_less_wait():
    # cycles of 100, 110, 120 ms, each waiting 90 ms: 10, 20, 30 ms
    host = _steps([0.1, 0.2, 0.31, 0.43], 0.09)
    assert _reader()(_ctx(host)) == pytest.approx(20.0)


def test_step_host_ms_skips_a_cycle_holding_an_admission():
    E = trace_reduce.Ev
    host = _steps([0.1, 0.2, 0.31, 0.43, 0.6], 0.09)
    host.append(E(0.45, 0.55, "repro.admit"))     # in the 0.43-0.6 cycle
    assert _reader()(_ctx(host)) == pytest.approx(20.0)


def test_step_host_ms_counts_only_steps_in_the_window():
    host = _steps([0.1, 0.2, 0.31, 0.43], 0.09)
    assert _reader()(_ctx(host, window=(0.15, 1.0))) == pytest.approx(25.0)


@pytest.mark.parametrize("host", [
    _steps([0.1], 0.09),                                  # one step
    _steps([0.1, 0.2], 0.09),                             # one cycle
    [h for h in _steps([0.1, 0.2, 0.3], 0.09)
     if h.name != "repro.step.wait"],                     # no wait spans
], ids=["one-step", "one-cycle", "no-wait-spans"])
def test_step_host_ms_gives_none_without_two_cycles(host):
    assert _reader()(_ctx(host)) is None


def test_step_host_ms_gives_none_without_a_device():
    ctx = _ctx(_steps([0.1, 0.2, 0.31, 0.43], 0.09))
    ctx.trace.n_devices = 0                   # a rehearsal on the CPU
    assert _reader()(ctx) is None


# ------------------------------------------------------------ program names --

@pytest.mark.parametrize("kind", ["grid", "paged"])
def test_programs_carry_stable_names(setup, kind):
    cfg, model, params, policy = setup
    watcher = NumericsWatcher(policy=policy, every=1000)
    if kind == "paged":
        eng = _paged(setup, numerics=watcher)
    else:
        eng = ContinuousBatchingEngine(model, params, policy, max_slots=2,
                                       S_max=64, numerics=watcher)
    args = (eng.params, eng.last_token, eng.cache)
    toks = np.zeros((1, 8), np.int32)
    for fn, name, a in ((eng._decode, "jit_decode_step", args),
                        (eng._decode_probed, "jit_decode_step_probed", args),
                        (eng._prefill, "jit_prefill", (eng.params, toks, {}))):
        text = fn.lower(*a).as_text()
        assert text.startswith(f"module @{name} "), text[:80]


# ------------------------------------------------------- kv_utilization ----

def test_paged_kv_utilization_counts_pool_blocks(setup):
    cfg = setup[0]
    reg = MetricsRegistry()
    eng = _paged(setup, metrics=reg)
    bt = eng.geom.block_tokens
    # three disjoint prompts of bt + 3 tokens: after one step each row
    # holds bt + 4 tokens in two blocks, 6 of the pool's 24
    for r in _requests(cfg, (bt + 3,) * 3):
        eng.submit(r)
    assert eng.admit() == 3
    eng.step()
    assert reg.gauge("kv_utilization").val == pytest.approx(6 / 24)
    assert eng.prefix_stats()["live"] == 6


def test_grid_kv_utilization_counts_rows(setup):
    cfg, model, params, policy = setup
    reg = MetricsRegistry()
    eng = ContinuousBatchingEngine(model, params, policy, max_slots=2,
                                   S_max=64, metrics=reg)
    for r in _requests(cfg, (10, 20)):
        eng.submit(r)
    assert eng.admit() == 2
    eng.step()
    assert reg.gauge("kv_utilization").val == pytest.approx(32 / 128)
