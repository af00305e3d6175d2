#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``configs/<config>.json``) under a traffic mix (``traffic/<mix>.json``).
The run builds the server on this machine's chip, warms every shape up
(set-up), measures for ``--seconds``, ends the requests still in flight
(a closed loop cancels them, an open loop drains them), reads the device's
peak memory, frees the server and checks a sample of the requests finished
in the window against the plain reference.  ``--trace 1`` also traces a
part of the window with the JAX profiler and reports the cell's per-layer
metrics (``metrics/<metric>.py``) instead of its end-to-end ones.

The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` ``breakdown``), then ``compared``: each number the
correctness check compared, beside its limit.  Standard error names the
device first and ends with the same numbers.  The run refuses any platform
but a TPU, and fewer chips than the cell asks for: it exits nonzero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import context  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402


class NoChip(Exception):
    pass


def log(**kw) -> None:
    print(json.dumps(kw), file=sys.stderr, flush=True)


def load_cell(workload: str):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return bench, cell, json.loads((ROOT / conf["file"]).read_text())


def reported(bench: dict, workload: str, trace: bool) -> list:
    """The metrics this cell reports in this kind of run."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [x for x in group if workload in x.get("workloads", [workload])]


def read_metric(name: str, ctx):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def setup_jax(chips: int, platform: str):
    import jax
    devs = jax.devices()
    if devs[0].platform == "tpu":
        # every program in the cache, so that only a checkout's first run
        # compiles; the directory is fixed (the path is part of the key)
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(device=info)
    if devs[0].platform != platform:
        raise NoChip(f"no {platform.upper()}: JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips], info


def main(argv=None, *, platform: str = "tpu") -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--test-size", action="store_true",
                    help="the configuration's and the mix's small sizes "
                         "(rehearsal on the CPU)")
    args = ap.parse_args(argv)

    bench, wl, cfg = load_cell(args.workload)
    try:
        devices, info = setup_jax(wl["chips"], platform)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    import peaks
    pk = peaks.peaks_for(info["kind"]) if platform == "tpu" else \
        {"flops": 1.0, "hbm_bw": 1.0}
    m = {**cfg, **(cfg.get("test_size", {}) if args.test_size else {})}
    if not args.test_size:
        harness.check_registered(cfg, harness.model_cfg(m, cfg))
    mix = traffic.load(wl["traffic"], test_size=args.test_size)
    cell = harness.Cell(args.workload, cfg, m, mix, args.seed)
    reqs = traffic.Requests(mix, args.seed, m["vocab_size"])

    srv = harness.Server(cell)
    harness.warm_up(srv, reqs, timeout=600)
    compiles = harness.CompileCounter()
    trace_dir = None
    if args.trace:
        trace_dir = str(ROOT / ".bench_out" / "trace" /
                        f"{args.workload}-{args.seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = harness.drive(srv, reqs, args.seconds, trace_dir=trace_dir,
                        compiles=compiles)
    setup_s = win.t0 - T_START
    srv.stop()
    info["memory_peak_bytes"] = harness.peak_bytes(devices)
    done = list(srv.engine.completions)
    steps, prefills = context.records(done, srv.offset)
    ctx = context.Context(cell=cell, m=m, mix=mix, win=win,
                          engine_completions=done, steps=steps,
                          prefills=prefills, setup_s=setup_s, peaks=pk,
                          n_devices=len(devices),
                          admissions=[(c.admitted_time + srv.offset,
                                       c.token_times[0] + srv.offset)
                                      for c in done if c.token_times])
    del srv
    gc.collect()

    failed = sum(not (r.ok or r.cut_short) for r in win.attempted)
    log(setup_s=setup_s, window_s=win.t1 - win.t0, attempted=len(win.attempted),
        failed=failed, cut_at_close=sum(r.cut_short for r in win.all),
        finished_in_window=len(win.finished()),
        compiles_in_window=win.compiles,
        generator_late_p99_ms=ctx.lateness_p99_ms())
    result = {"correct": False, "attempted": len(win.attempted),
              "failed": failed, "metrics": {}, "device": info}
    if args.trace:
        ctx.load_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        info["busy_s"] = ctx.busy_s()
        info["window_s"] = ctx.trace_window[1] - ctx.trace_window[0]
        result["breakdown"] = ctx.breakdown()
    for spec in reported(bench, args.workload, bool(args.trace)):
        v = read_metric(spec["name"], ctx)
        if v is not None:
            result["metrics"][spec["name"]] = {"value": v,
                                               "unit": spec["unit"]}

    import check
    t = time.perf_counter()
    picked = check.sample(win.finished(), args.seed)
    gaps = check.served_gaps(m, args.seed, picked,
                             mix["s_max"])[0] if picked else []
    readings = check.numbers(gaps)
    ok, compared = check.compare(
        readings, check.limits(args.workload, test_size=args.test_size))
    log(reference_s=time.perf_counter() - t, sampled_requests=len(picked),
        sampled_tokens=int(len(gaps)), readings=readings)
    result["correct"] = ok
    for k, v in compared.items():
        log(compared=k, value=v["value"], limit=v["limit"])
    result["compared"] = compared
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
