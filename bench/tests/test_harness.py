"""Tests of the benchmark's own code, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They check the yardstick: trace reduction, traffic generation, the work
counts, the refusal of any platform but a TPU, a whole rehearsal of each
cell, and that the correctness check fails the control and a broken timed
path.
"""
from __future__ import annotations

import io
import json
import math
import pathlib
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import check  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

WORKLOADS = [w["name"] for w in
             json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


# ------------------------------------------------------------ reduction ---

def small_trace():
    """Two decode runs and a prefill run on the device; host spans around
    their launches; a gap under a host step span."""
    E = tr.Ev
    host = [E(0.000, 0.100, "bench.window"),
            E(0.010, 0.012, "repro.decode_step", depth=1),
            E(0.030, 0.032, "repro.prefill", depth=1),
            E(0.050, 0.052, "repro.decode_step", depth=1),
            E(0.070, 0.090, "bench.engine.step", depth=1)]
    modules = [E(0.011, 0.025, "jit__lambda", "jit__lambda#1"),
               E(0.031, 0.045, "jit__lambda", "jit__lambda#2"),
               E(0.051, 0.065, "jit__lambda", "jit__lambda#1"),
               E(0.066, 0.067, "jit_argmax", "jit_argmax#3")]
    ops = [E(0.011, 0.025, "while.9"),
           E(0.011, 0.018, "fusion.1"),
           E(0.018, 0.025, "fusion.2"),
           E(0.031, 0.045, "convolution.3"),
           E(0.051, 0.065, "fusion.1"),
           E(0.066, 0.067, "argmax")]
    return tr.Trace(ops, modules, host, n_devices=1)


def test_union_merges_and_clips():
    assert tr.union([(0, 2), (1, 3), (5, 6), (7, 9)], 0.5, 8) == \
        [[0.5, 3], [5, 6], [7, 8]]


def test_busy_is_the_union_of_operations():
    t = small_trace()
    assert t.n_devices == 1
    assert tr.busy_s(t, 0.0, 0.1) == pytest.approx(0.014 + 0.014 + 0.014
                                                   + 0.001)


def test_idle_gaps_are_labelled_by_the_open_host_span():
    gaps = tr.idle_gaps(small_trace(), 0.0, 0.1)
    assert gaps[0] == ["bench.engine.step", pytest.approx(0.033)]
    assert ["none", pytest.approx(0.011)] in gaps
    assert sum(g for _, g in gaps) == pytest.approx(0.1 - 0.043)


def test_programs_are_attributed_by_the_launching_annotation():
    t = small_trace()
    dec = tr.program_runs(t, "repro.decode_step", 0.0, 0.1)
    assert [x.start for x in dec] == [0.011, 0.051]
    pre = tr.program_runs(t, "repro.prefill", 0.0, 0.1)
    assert [x.start for x in pre] == [0.031]
    top = tr.top_ops(t, 0.0, 0.1)
    assert top[0] == ["fusion.1", pytest.approx(0.021)]
    assert "while.9" not in dict(top)      # a loop counts through its body


# -------------------------------------------------------------- traffic ---

OPEN = {"loop": "open", "rate": 4.0, "ramp_s": 1, "max_slots": 4,
        "s_max": 96, "kv_pool_tokens": 384, "max_queue": 64,
        "prompt": {"mean": 40, "sigma": 0.5, "buckets": [32, 64]},
        "output": {"mean": 9, "sigma": 0.5, "min": 4, "max": 16},
        "block": 20, "drain_s": 60, "name": "open-test"}


def mixes():
    return [traffic.load("chat-closed"), OPEN]


@pytest.mark.parametrize("mix", mixes(), ids=lambda m: m["name"])
def test_traffic_is_deterministic_and_the_same_work_per_seed(mix):
    m = mix
    a, b = traffic.Requests(m, 2**40 + 3, 32064), \
        traffic.Requests(m, 2**40 + 3, 32064)
    c = traffic.Requests(m, 17, 32064)
    assert a.spec(5) == b.spec(5) and a.spec(5) != c.spec(5)
    n = m["block"]
    for k in range(3):              # every block holds the whole multiset
        la = [(len(p), o) for p, o in map(a.spec, range(k * n, k * n + n))]
        lc = [(len(p), o) for p, o in map(c.spec, range(k * n, k * n + n))]
        assert sorted(x for x, _ in la) == sorted(a.prompt_len) == \
            sorted(x for x, _ in lc)
        assert sorted(y for _, y in la) == sorted(a.max_new) == \
            sorted(y for _, y in lc)
    assert la != lc                  # in the seed's own order
    p, o = m["prompt"], m["output"]
    assert set(a.prompt_len) <= set(p["buckets"])
    assert a.warmup_lengths() == sorted(set(a.prompt_len.tolist()))
    assert a.max_new.min() >= o["min"] and a.max_new.max() <= o["max"]
    assert a.max_new.max() + max(p["buckets"]) <= m["s_max"]
    # the quantiles' mean is the distribution's, before snapping and clipping
    for spec in (p, o):
        q = traffic._lognormal(spec, 4096)
        assert q.mean() == pytest.approx(spec["mean"], rel=0.02)
        assert np.median(q) == pytest.approx(
            spec["mean"] * math.exp(-spec["sigma"] ** 2 / 2), rel=1e-3)
    if m["loop"] == "open":
        ta, tc = a.arrivals(30), c.arrivals(30)
        assert len(ta) == len(tc) == round(m["rate"] * (m["ramp_s"] + 30))
        assert np.array_equal(ta, b.arrivals(30))
        assert sorted(np.diff(ta)) == pytest.approx(sorted(np.diff(tc)),
                                                    rel=0.2, abs=1.0)


def test_open_loop_drive_and_its_readers():
    """The open-loop driver (a mix file with ``"loop": "open"``) and the
    readers of its latency metrics, on the CPU at a small size."""
    import context
    run.setup_jax(1, "cpu")
    cfg = config("phi3-mini-3.8b-p8")
    m = {**cfg, **cfg["test_size"]}
    cell = harness.Cell("open-test", cfg, m, OPEN, 5)
    reqs = traffic.Requests(OPEN, 5, m["vocab_size"])
    srv = harness.Server(cell)
    harness.warm_up(srv, reqs, timeout=600)
    win = harness.drive(srv, reqs, 3.0, trace_dir=None,
                        compiles=harness.CompileCounter())
    srv.stop()
    assert len(win.attempted) == round(OPEN["rate"] * 3.0) or \
        abs(len(win.attempted) - OPEN["rate"] * 3.0) <= 2
    assert all(r.ok for r in win.attempted) and win.lateness
    done = list(srv.engine.completions)
    steps, fills = context.records(done, srv.offset)
    ctx = context.Context(cell=cell, m=m, mix=OPEN, win=win,
                          engine_completions=done, steps=steps,
                          prefills=fills, setup_s=1.0, peaks={}, n_devices=1)
    for name in ("ttft_p90_ms", "queue_wait_p90_ms", "plane_delay_p50_ms",
                 "output_tok_s", "itl_p95_ms", "slot_occupancy"):
        v = run.read_metric(name, ctx)
        assert v is not None and math.isfinite(v) and v >= 0, name
    assert ctx.lateness_p99_ms() < 1000


def test_steps_and_prefills_are_rebuilt_from_the_request_records():
    """Two requests: prompt 5 admitted at 1.0 (first token at 1.5), decoded
    by steps at 2 and 3; prompt 7 admitted at 2.2, decoded by the step at 3.
    """
    import context
    from repro.launch.engine import Completion
    a = Completion(rid=0, prompt_len=5, tokens=[1, 2, 3], arrival_time=0.5,
                   admitted_time=1.0, finished_time=3.0,
                   token_times=[1.5, 2.0, 3.0])
    b = Completion(rid=1, prompt_len=7, tokens=[4, 5], arrival_time=2.0,
                   admitted_time=2.2, finished_time=3.0,
                   token_times=[2.5, 3.0])
    steps, fills = context.records([a, b], 10.0)
    assert steps == [(12.0, [6]), (13.0, [7, 8])]
    assert fills == [(11.0, 5), (12.2, 7)]
    ctx = context.Context(cell=None, m={}, mix={}, win=None,
                          engine_completions=[a, b], steps=steps,
                          prefills=fills, setup_s=0.0, peaks={}, n_devices=1,
                          admissions=[(11.0, 11.5), (12.2, 12.5)])
    assert ctx.admitting(11.2) and not ctx.admitting(11.7)


def test_a_step_run_as_several_programs_counts_once():
    import context
    t = small_trace()
    ctx = context.Context(cell=None, m={}, mix={}, win=None,
                          engine_completions=[], setup_s=0.0, peaks={},
                          n_devices=1, steps=[(0.009, [5, 6])],
                          prefills=[(0.029, 64)], trace=t,
                          trace_window=(0.0, 0.1))
    took, steps = ctx.unique("repro.decode_step", ctx.steps)
    assert took == pytest.approx(0.028) and steps == [[5, 6]]
    took, fills = ctx.unique("repro.prefill", ctx.prefills)
    assert took == pytest.approx(0.014) and fills == [64]


# ----------------------------------------------------------------- work ---

def test_work_phi3_by_hand():
    m = config("phi3-mini-3.8b-p8")
    d, f, L, V = 3072, 8192, 32, 32064
    per_layer = 4 * d * d + 3 * d * f
    assert work.active_params(m) == L * per_layer + d * V
    lens = [100, 300]
    w = work.decode_step(m, lens)
    kv_row = 2 * 32 * 96               # K and V codes of a token, per layer
    assert w["bytes"] == L * per_layer + d * V + L * kv_row * (400 + 2)
    assert w["flops"] == 2 * 2 * (L * per_layer + d * V) \
        + 4 * 32 * 96 * 400 * L
    p = work.prefill(m, 512)
    assert p["flops"] == 2 * L * per_layer * 512 + 2 * d * V \
        + 4 * 32 * 96 * L * 512 * 513 / 2
    assert p["bytes"] == L * per_layer + d * V + L * kv_row * 512


def test_work_olmoe_by_hand():
    m = config("olmoe-1b-7b-p8")
    d, f, L, V, E, k = 2048, 1024, 16, 50304, 64, 8
    fixed = 4 * d * d + d * E
    assert work.active_params(m) == L * (fixed + k * 3 * d * f) + d * V
    reach1 = E * (1 - (1 - k / E) ** 1)
    assert reach1 == pytest.approx(k)
    w = work.decode_step(m, [10])
    assert w["bytes"] == pytest.approx(
        L * (fixed + k * 3 * d * f) + d * V + L * 2 * 16 * 128 * 11)
    reach32 = E * (1 - (1 - k / E) ** 32)
    assert work.weight_bytes(m, 32, 1.0) == pytest.approx(
        L * (fixed + reach32 * 3 * d * f) + d * V)
    assert 63 < reach32 < 64
    peaks = {"flops": 197e12, "hbm_bw": 819e9}
    assert work.min_time(w, peaks) == pytest.approx(w["bytes"] / 819e9)


# ------------------------------------------------------------ the runs ---

def run_cell(argv, **kw):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(argv, **kw)
    lines = [x for x in buf.getvalue().splitlines() if x.strip()]
    return rc, lines


def test_the_harness_refuses_the_cpu(capsys):
    rc, lines = run_cell(["--workload", WORKLOADS[0], "--seed", "1",
                          "--seconds", "1", "--test-size"])
    assert rc != 0
    assert lines == []                      # no result, not even a line
    err = capsys.readouterr().err.splitlines()
    assert json.loads(err[0])["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_a_result_line(workload, trace):
    rc, lines = run_cell(["--workload", workload, "--seed", str(2**33 + 5),
                          "--seconds", "3", "--trace", str(trace),
                          "--test-size"], platform="cpu")
    assert rc == 0
    res = json.loads(lines[-1])
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    want = {x["name"] for x in run.reported(bench, workload, bool(trace))}
    got = set(res["metrics"])
    if not trace:
        assert got == want
    else:
        # device metrics need a device trace; the CPU has none
        assert {"slot_occupancy"} & want <= got <= want
        assert "breakdown" in res and "busy_s" in res["device"]
    for v in res["metrics"].values():
        assert math.isfinite(v["value"])


# --------------------------------------------------- correctness control ---

@pytest.mark.parametrize("workload", WORKLOADS)
def test_the_control_fails_and_the_program_passes(workload):
    """The reference with int4 weights, put in the program's place, reads
    above the cell's limit on every seed; the program reads below it."""
    import control
    _, wl, cfg = run.load_cell(workload)
    m = {**cfg, **cfg["test_size"]}
    mix = traffic.load(wl["traffic"], test_size=True)
    limits = check.limits(workload, test_size=True)
    for seed in (21, 2**35 + 22, 23):
        r = control.one_seed(wl, cfg, m, mix, seed, 2.0)
        assert check.compare(r["program"], limits)[0], r
        assert not check.compare(r["control"], limits)[0], r


def _break_tokens(engine):
    vocab = engine.model.cfg.vocab
    sample = engine._next_token
    engine._next_token = lambda logits: (sample(logits) + 1) % vocab


def _break_state(engine):
    import jax
    import jax.numpy as jnp
    dec = engine._decode

    def unchanged(p, t, c):
        kv = jax.tree.map(jnp.copy, c["kv"])
        logits, new = dec(p, t, c)
        new["kv"] = kv
        return logits, new
    engine._decode = unchanged


@pytest.mark.parametrize("fault", [_break_tokens, _break_state],
                         ids=["token_altered", "state_unchanged"])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_a_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    base = harness.Server.__init__

    def broken(self, cell):
        base(self, cell)
        fault(self.engine)
    monkeypatch.setattr(harness.Server, "__init__", broken)
    rc, lines = run_cell(["--workload", workload, "--seed", "31",
                          "--seconds", "3", "--test-size"], platform="cpu")
    assert rc == 0
    res = json.loads(lines[-1])
    assert res["correct"] is False, res["compared"]
