#!/usr/bin/env python3
"""Smoke run of the serving main path on one TPU chip.

    python3 chip_smoke.py            # phi3-mini-3.8b at published widths

One process, one chip, weights made from ``--seed``.  Phases, each printing
one JSON line (``{"phase": ...}``):

  device     platform, device_kind and count as JAX reports them; anything
             but a TPU fails here (nothing falls back to the CPU).
  kernel     the compiled Pallas decode-attention kernel against the tiled
             XLA path (``posit_decode_attention_tiled``) on one seeded p8 KV
             cache at phi3 decode widths with ragged lengths.
  server     ``launch.server.build_server`` (paged continuous engine,
             p8-serve policy, posit-coded weights) started in this process;
             concurrent ``/v1/generate`` requests over localhost, one of them
             streamed.  Any ``server/error`` or a stream that does not finish
             with ``max_new``/``eos`` fails.
  slot_grid  ``launch.serve.run`` on the slot-grid continuous engine with
             ``attn_impl=kernel``: the Pallas kernel decodes every step, and
             the lowered decode step must hold a ``tpu_custom_call``.

The last line of stdout is ``{"ok": true, "device": {...}}``; any failed
phase exits nonzero before it.  Numbers on earlier lines (times, bytes) are
observations of one run, not benchmark metrics.  ``--reduced`` runs the CI
sizes of the same configuration (for rehearsing the script on the CPU, which
tests do by calling :func:`main` with ``platform="cpu"``).
"""
from __future__ import annotations

import argparse
import concurrent.futures
import gc
import http.client
import json
import pathlib
import sys
import threading
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.core.codec import posit_encode  # noqa: E402
from repro.launch.config import ServeConfig, use_compile_cache  # noqa: E402

ARCH = "phi3-mini-3.8b"
POLICY = "p8-serve"          # p8_0 weights, p8_0 KV cache, bf16 compute
MAX_SLOTS, PROMPT_LEN, GEN = 8, 512, 32
KERNEL_S = 2048              # KV length of the kernel phase's cache
BLOCK_TOKENS = 16            # tokens per KV page of the paged server
FINISHED = ("max_new", "eos")


class SmokeFailure(Exception):
    pass


def _check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _emit(phase: str, **kw) -> None:
    print(json.dumps({"phase": phase, **kw}), flush=True)


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# --------------------------------------------------------------- phases ----

def phase_device(platform: str):
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    _emit("device", **info)
    _check(dev.platform == platform,
           f"no {platform.upper()}: JAX found platform {dev.platform!r} "
           f"({dev.device_kind})")
    return dev, info


def phase_kernel(cfg, rng, S: int) -> None:
    """Compiled kernel vs the tiled XLA path on the same p8 cache.

    Tolerance: both paths run their f32 matmuls at the TPU's default
    precision, which rounds operands to bf16 (relative error 2^-9).  The
    probabilities are rounded in both paths and the scaled query in the
    tiled one; with scores |s| <= 4 the softmax weights move by under 1%,
    and the outputs, convex combinations of V rows, by at most
    2^-6 * max|V|.  A wrong head mapping or mask moves them by O(max|V|).
    """
    from repro.kernels.posit_attention import ops as attn_ops

    B, H, d = MAX_SLOTS, cfg.n_kv, cfg.hd
    es = 0
    q = jnp.asarray(rng.normal(0, 1, (B, cfg.n_heads, d)), jnp.bfloat16)
    k = posit_encode(jnp.asarray(rng.normal(0, 1, (B, H, S, d)), jnp.float32),
                     8, es)
    v = posit_encode(jnp.asarray(rng.normal(0, 1, (B, H, S, d)), jnp.float32),
                     8, es)
    # ragged: one free slot (length 0), one full row, the rest in between
    lengths = rng.integers(1, S, B).astype(np.int32)
    lengths[0], lengths[-1] = 0, S
    lengths = jnp.asarray(lengths)
    t0 = time.perf_counter()
    got = attn_ops.decode_attention(q, k, v, lengths, es, kv_bits=8,
                                    impl="pallas")
    got = np.asarray(got, np.float32)
    kernel_s = time.perf_counter() - t0
    want = np.asarray(attn_ops.posit_decode_attention_tiled(
        q, k, v, lengths, es, kv_bits=8), np.float32)
    v_max = float(np.nanmax(np.abs(np.asarray(
        attn_ops.posit_decode(v, 8, es)))))
    tol = 2.0 ** -6 * v_max
    diff = float(np.max(np.abs(got - want)))
    _emit("kernel", B=B, Hq=cfg.n_heads, Hkv=H, d=d, S=S,
          lengths=[int(x) for x in lengths], max_abs_diff=diff, tol=tol,
          first_call_s=kernel_s)
    _check(np.isfinite(got).all(), "kernel output is not finite")
    _check(not got[0].any(), "length-0 row is not exact zeros")
    _check(diff <= tol, f"kernel differs from the tiled path by {diff} > {tol}")


def _http(port: int, method: str, path: str, body=None, timeout=60.0):
    """One request to the server on localhost: (status, body bytes)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, None if body is None else json.dumps(body))
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _generate(port: int, prompt: list, stream: bool) -> dict:
    """One request; returns {"tokens": [...], "finish_reason": ...}."""
    st, body = _http(port, "POST", "/v1/generate",
                     {"prompt": prompt, "max_new_tokens": GEN,
                      "stream": stream}, timeout=900)
    _check(st == 200, f"/v1/generate answered {st}: {body[:200]!r}")
    if not stream:
        comp = json.loads(body)
        return {"tokens": comp["tokens"],
                "finish_reason": comp["finish_reason"]}
    events = [json.loads(line) for line in body.decode().splitlines()]
    _check(events and events[-1]["event"] == "finish",
           "stream ended without a finish event")
    return {"tokens": [e["token"] for e in events if e["event"] == "token"],
            "finish_reason": events[-1]["finish_reason"]}


def _check_completion(c: dict, vocab: int) -> None:
    _check(c["finish_reason"] in FINISHED,
           f"request finished with {c['finish_reason']!r}")
    n = len(c["tokens"])
    _check(n == GEN if c["finish_reason"] == "max_new" else 1 <= n <= GEN,
           f"{n} tokens for finish_reason {c['finish_reason']!r}")
    _check(all(0 <= t < vocab for t in c["tokens"]),
           f"token ids outside the vocabulary [0, {vocab})")


def phase_server(scfg: ServeConfig, cfg, dev, rng) -> None:
    import asyncio

    from repro.launch.server import build_server

    loop = asyncio.new_event_loop()
    box: dict = {}
    started = threading.Event()

    def serve() -> None:
        asyncio.set_event_loop(loop)
        try:
            box["server"] = build_server(scfg)
            loop.run_until_complete(box["server"].start())
        except Exception as e:  # re-raised by the main thread
            box["error"] = e
            return
        finally:
            started.set()
        loop.run_forever()

    t_load = time.perf_counter()
    thread = threading.Thread(target=serve, daemon=True, name="smoke-server")
    thread.start()
    started.wait()
    if "error" in box:
        raise box["error"]
    server = box["server"]
    load_s = time.perf_counter() - t_load
    try:
        prompts = [rng.integers(0, cfg.vocab, PROMPT_LEN).tolist()
                   for _ in range(MAX_SLOTS + 1)]
        # first request alone: it pays the prefill and decode compiles
        t0 = time.perf_counter()
        first = _generate(server.port, prompts[0], stream=False)
        compile_s = time.perf_counter() - t0
        # then a full slot grid at once, one of them streamed
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(MAX_SLOTS) as pool:
            futs = [pool.submit(_generate, server.port, p, i == 0)
                    for i, p in enumerate(prompts[1:])]
            rest = [f.result() for f in futs]
        batch_s = time.perf_counter() - t0
        stats = json.loads(_http(server.port, "GET", "/v1/stats")[1])
    finally:
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(60)
        loop.call_soon_threadsafe(loop.stop)
        thread.join(60)
    done = [first] + rest
    for c in done:
        _check_completion(c, cfg.vocab)
    _emit("server", requests=len(done), streamed=1,
          tokens=sum(len(c["tokens"]) for c in done),
          finish_reasons=sorted({c["finish_reason"] for c in done}),
          load_s=load_s, compile_s=compile_s, batch_s=batch_s,
          errors=stats["errors"], peak_bytes_in_use=_peak_bytes(dev))
    _check(stats["errors"] == 0 and server.driver.errors == 0,
           f"server/error: the drive loop caught {server.driver.errors} "
           "engine exceptions")


def phase_slot_grid(scfg: ServeConfig, dev, platform: str) -> None:
    from repro.launch import serve
    from repro.models.layers import quantize_params
    from repro.models.registry import build_model

    report = serve.run(scfg)
    reasons = report["finish_reasons"]
    _check(report["requests"] == scfg.requests,
           f"{report['requests']} of {scfg.requests} requests completed")
    _check(set(reasons) <= set(FINISHED), f"finish reasons {reasons}")
    _check(report["tokens"] >= scfg.requests, "no tokens served")

    # the decode step the engine ran, lowered again from shapes: on the chip
    # the attention must be the compiled Pallas kernel, not interpret mode
    policy, _ = scfg.build_policy()
    model = build_model(scfg.arch_cfg())
    params = jax.eval_shape(
        lambda k: quantize_params(model.init(k), policy),
        jax.random.key(scfg.seed))
    cache = jax.eval_shape(lambda: model.init_cache(
        scfg.max_slots, scfg.s_max(model.cfg), policy))
    tok = jax.ShapeDtypeStruct((scfg.max_slots,), jnp.int32)
    text = jax.jit(lambda p, t, c: model.decode_step(p, t, c, policy)) \
        .lower(params, tok, cache).as_text()
    kernel = "tpu_custom_call" in text
    _emit("slot_grid", requests=report["requests"], tokens=report["tokens"],
          finish_reasons=reasons, compile_s=report["compile_s"],
          decode_steps=report["decode_steps"], pallas_kernel=kernel,
          peak_bytes_in_use=_peak_bytes(dev))
    if platform == "tpu":
        _check(kernel, "decode step holds no tpu_custom_call: the attention "
                       "kernel did not lower for the chip")


# ----------------------------------------------------------------- main ----

def main(argv=None, *, platform: str = "tpu") -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reduced", action="store_true",
                    help="CI-sized configuration (rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        dev, info = phase_device(platform)
        cfg = get_arch(ARCH)
        cfg = cfg.reduced() if args.reduced else cfg
        rng = np.random.default_rng(args.seed)
        phase_kernel(cfg, rng, 256 if args.reduced else KERNEL_S)
        common = dict(arch=ARCH, reduced=args.reduced, continuous=True,
                      policy=POLICY, quantize_weights=True,
                      max_slots=MAX_SLOTS, prompt_len=PROMPT_LEN, gen=GEN,
                      seed=args.seed)
        # p8 KV: one code byte per element of K and V
        page_bytes = BLOCK_TOKENS * 2 * cfg.n_kv * cfg.hd
        phase_server(ServeConfig(paged=True, page_bytes=page_bytes, port=0,
                                 **common).validate(), cfg, dev, rng)
        gc.collect()
        phase_slot_grid(ServeConfig(attn_impl="kernel", requests=MAX_SLOTS,
                                    **common).validate(), dev, platform)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    use_compile_cache()
    sys.exit(main())
