"""Beyond-paper: posit-compressed cross-pod gradient collective.

Runs in a subprocess with 8 simulated host devices (mesh (2,4) =
("pod","data")) so the parent process keeps its single-device view. Reports:
  * wall time f32 psum vs posit-compressed psum (CPU: indicative only)
  * HLO collective payload bytes on the pod axis (deterministic — the claim)
  * error-feedback quality: compressed-sum relative error with/without EF
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.types import P16_1, P8_0
from repro.distributed.collectives import compressed_psum

mesh = jax.make_mesh((2, 4), ("pod", "data"))
N = 1 << 20
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(0, 1e-3, (8, N)).astype(np.float32))  # grad-like

def run(fmt):
    def f(x):
        y, res = compressed_psum(x, fmt, intra_axis="data", inter_axis="pod")
        return y
    sm = jax.shard_map(f, mesh=mesh, in_specs=P(("pod", "data")),
                       out_specs=P(("pod", "data")), check_vma=False)
    jf = jax.jit(sm)
    lo = jf.lower(x)
    txt = lo.compile().as_text()
    coll_bytes = {}
    for line in txt.splitlines():
        for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all"):
            if f" {op}(" in line or f" {op}-start(" in line:
                import re
                for dt, dims in re.findall(r"\b(f32|bf16|u8|u16|s32)\[([0-9,]*)\]",
                                            line.split(op)[0]):
                    n = 1
                    for d in dims.split(","):
                        if d: n *= int(d)
                    sz = {"f32": 4, "bf16": 2, "u8": 1, "u16": 2, "s32": 4}[dt]
                    coll_bytes[dt] = coll_bytes.get(dt, 0) + n * sz
    out = jf(x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(jf(x))
    us = (time.perf_counter() - t0) / 5 * 1e6
    # exactness vs true sum
    true = np.asarray(x, np.float64).reshape(8, N).sum(0)
    got = np.asarray(out, np.float64)[0]
    rel = float(np.abs(got - true).mean() / (np.abs(true).mean() + 1e-12))
    return {"us": us, "coll_bytes": coll_bytes, "rel_err": rel}

res = {"f32": run(None), "p16": run(P16_1), "p8": run(P8_0)}

# error feedback over steps: EF should beat no-EF on accumulated updates
def ef_trial(use_ef):
    fmt = P8_0
    res_buf = jnp.zeros((8, N // 64), jnp.float32)
    acc_c = np.zeros(N // 64); acc_t = np.zeros(N // 64)
    xs = rng.normal(0, 1e-3, (20, 8, N // 64)).astype(np.float32)
    def f(x, r):
        y, r2 = compressed_psum(x, fmt, intra_axis="data", inter_axis="pod",
                                residual=r if use_ef else None)
        return y, (r2 if use_ef and r2 is not None else jnp.zeros_like(x))
    sm = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P(("pod", "data")),) * 2,
                 out_specs=(P(("pod", "data")),) * 2, check_vma=False))
    for i in range(20):
        y, res_buf = sm(jnp.asarray(xs[i]), res_buf)
        acc_c += np.asarray(y, np.float64)[0]
        acc_t += xs[i].astype(np.float64).reshape(8, -1).sum(0)
    return float(np.abs(acc_c - acc_t).mean() / np.abs(acc_t).mean())

res["ef_err"] = ef_trial(True)
res["noef_err"] = ef_trial(False)
print("RESULT " + json.dumps(res))
"""


def run():
    # the child runs on virtual CPU devices: on a TPU host the parent process
    # already holds the chip, and a child that reached for it would fail
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")]
    if not line:
        emit("collectives/error", 0.0, out.stderr[-200:].replace("\n", " "))
        return False
    res = json.loads(line[0][7:])
    f32b = sum(res["f32"]["coll_bytes"].values())
    for k in ("f32", "p16", "p8"):
        r = res[k]
        tot = sum(r["coll_bytes"].values())
        emit(f"collectives/psum_{k}", r["us"],
             f"bytes={tot} vs_f32={tot / max(f32b, 1):.2f}x rel_err={r['rel_err']:.2e}")
    emit("collectives/error_feedback_gain", 0.0,
         f"ef={res['ef_err']:.2e} no_ef={res['noef_err']:.2e} "
         f"better={res['ef_err'] < res['noef_err']}")
    return True


if __name__ == "__main__":
    run()
