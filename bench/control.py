#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

For each seed, in one process: the cell's server is built, warmed up and
driven by the cell's traffic for a short window (``--seconds``) at the
cell's own load, in-flight requests are ended as a run ends them, and a
sample is drawn as a benchmark run draws it.  Two numbers are read on the same prompts and
served tokens:

  program   the widest gap by which a served token's reference logit lies
            below the reference's best (what a run compares);
  control   the same gap for the token the control puts first at each
            position: the reference computed with int4 weights
            (``reference/common.py``), the precision step below the
            configuration's 8-bit codes.

The limit in ``limits/<cell>.json`` lies between the largest program reading
over a dozen seeds and more and the smallest control reading.  This script
is not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run

import check
import harness
import traffic


def one_seed(wl, cfg, m, mix, seed: int, seconds: float) -> dict:
    cell = harness.Cell(wl["name"], cfg, m, mix, seed)
    reqs = traffic.Requests(mix, seed, m["vocab_size"])
    srv = harness.Server(cell)
    harness.warm_up(srv, reqs, timeout=600)
    win = harness.drive(srv, reqs, seconds, trace_dir=None,
                        compiles=harness.CompileCounter())
    srv.stop()
    del srv
    gc.collect()
    picked = check.sample(win.finished(), seed)
    failed = sum(not (r.ok or r.cut_short) for r in win.attempted)
    if not picked:
        return {"seed": seed, "requests": 0, "failed": failed}
    gaps, ctrl = check.served_gaps(m, seed, picked, mix["s_max"],
                                   quant="int4")
    return {"seed": seed, "requests": len(picked), "tokens": int(len(gaps)),
            "failed": failed,
            "program": check.numbers(gaps), "control": check.numbers(ctrl)}


def main(argv=None, *, platform: str = "tpu") -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--test-size", action="store_true")
    args = ap.parse_args(argv)
    _, wl, cfg = run.load_cell(args.workload)
    try:
        run.setup_jax(wl["chips"], platform)
    except run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    m = {**cfg, **(cfg.get("test_size", {}) if args.test_size else {})}
    mix = traffic.load(wl["traffic"], test_size=args.test_size)
    rows = []
    for s in args.seeds.split(","):
        rows.append(one_seed(wl, cfg, m, mix, int(s), args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    read = [r for r in rows if r["requests"]]
    print(json.dumps({
        "workload": args.workload, "seeds": len(read),
        "program_max": {k: max(r["program"][k] for r in read)
                        for k in read[0]["program"]} if read else None,
        "control_min": {k: min(r["control"][k] for r in read)
                        for k in read[0]["control"]} if read else None}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
