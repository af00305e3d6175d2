"""Parameter init + core layer ops (linear, norm, rotary, MLP).

Parameter convention: params are nested dicts of jnp arrays. Posit-stored
weights appear as ``{"w_codes": uintN, ...}`` after ``quantize_params``; float
weights as ``{"w": floatN}``. The TransPolicy (static) says how to interpret
them — mirroring how the paper's pcsr, not the register file, carries format.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.calib import observe
from repro.core.codec import posit_encode
from repro.core.dot import apply_epilogue, posit_dot, posit_matmul_wx
from repro.core.lut import decode_with_impl, encode_with_impl
from repro.core.pack import pack_p8, packed_decode_p8
from repro.core.pcsr import OperandSlots, TransPolicy
from repro.core.types import F32, PositFmt


def _compute_dtype(policy: TransPolicy):
    return jnp.float32 if policy.compute_dtype == "f32" else jnp.bfloat16


def resolve_policy(policy, path: str = "") -> TransPolicy:
    """Per-layer policy resolution (DESIGN.md §9).

    A ``PrecisionPolicy`` (core/policy.py) resolves through its rule list for
    the given layer path; a plain ``TransPolicy`` passes through unchanged.
    Every linear call site hands its path here, so one object can schedule
    p16 attention x packed-p8 MLP across a whole model.
    """
    resolve = getattr(policy, "policy_for", None)
    return resolve(path) if resolve is not None else policy


# ------------------------------------------------------------------ linear ----

def init_linear(key, d_in: int, d_out: int, *, bias: bool = False,
                scale: Optional[float] = None, dtype=jnp.float32) -> dict:
    if scale is None:
        scale = d_in ** -0.5
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def quantize_linear(p: dict, fmt: PositFmt, *, packed: bool = False) -> dict:
    """Convert a float linear param dict to posit storage (serving path).

    ``packed=True`` stores p8 codes two-per-uint16-lane (core/pack.py):
    half the weight words at rest and on the wire, identical numerics.
    """
    codes = posit_encode(p["w"].astype(jnp.float32), fmt.nbits, fmt.es)
    if packed:
        if fmt.nbits != 8:
            raise ValueError(f"packed weight storage requires p8, got {fmt}")
        q = {"w_packed": pack_p8(codes)}
    else:
        q = {"w_codes": codes}
    if "b" in p:
        q["b"] = p["b"]  # biases stay float: O(d) storage, numerically sensitive
    return q


def effective_weight(p: dict, policy: TransPolicy, es=None, path: str = "") -> jax.Array:
    """The weight as seen by the matmul datapath.

    * posit codes       -> decode (exact; bf16 target for p8); packed lanes
                           decode both bytes (bit-identical to unpacked)
    * float + posit pol -> straight-through quantize (training: master weights
                           stay f32, forward sees posit-rounded values)
    * float, no policy  -> as-is (IEEE bypass)
    """
    policy = resolve_policy(policy, path)
    if "w_packed" in p:
        fmt = policy.weights
        assert fmt is not None and fmt.nbits == 8, \
            "packed params need a p8 policy.weights"
        return packed_decode_p8(p["w_packed"], fmt.es if es is None else es,
                                codec_impl=policy.codec_impl)
    if "w_codes" in p:
        fmt = policy.weights
        assert fmt is not None, "posit-coded params need policy.weights"
        return decode_with_impl(p["w_codes"], fmt.nbits,
                                fmt.es if es is None else es, policy.codec_impl)
    w = p["w"]
    if observe.is_active():
        # calibration-mode forward (DESIGN.md §11): stream this site's float
        # weight statistics; the same path string keys the emitted rules
        observe.record(path, "weight", w)
    fmt = policy.weights
    if fmt is not None:
        wf = w.astype(jnp.float32)
        e = fmt.es if es is None else es
        qw = decode_with_impl(
            posit_encode(wf, fmt.nbits, e), fmt.nbits, e, policy.codec_impl)
        w = w + jax.lax.stop_gradient(qw - wf).astype(w.dtype)
    return w


def apply_linear(p: dict, x: jax.Array, policy: TransPolicy, es=None, *,
                 activation: str = "none",
                 residual: Optional[jax.Array] = None,
                 path: str = "") -> jax.Array:
    """y = act(x @ W + b) + residual, epilogue fused with the GEMM.

    Posit-coded weights route through ``posit_matmul_wx`` so the decode, the
    matmul and the whole epilogue stay one fused op (one kernel launch / HBM
    write on the serving path); packed-p8 storage ("w_packed") moves half the
    weight words and decodes both lanes in the same fused op.
    ``policy.epilogue == "chained"`` materializes every stage instead (the
    benchmark baseline).  ``path`` is this layer's name for per-layer
    ``PrecisionPolicy`` resolution (DESIGN.md §9).
    """
    policy = resolve_policy(policy, path)
    if observe.is_active():
        observe.record(path, "act", x)
        # training-plane channel (DESIGN.md §16): the cotangent dL/dx
        # arriving at this site streams to the "grad" histogram under
        # value_and_grad — a no-op unless the observer asked for gradients
        x = observe.grad_tap(path, x)
    from repro.obs import prof
    if not prof.is_active():
        return _linear_resolved(p, x, policy, es, activation=activation,
                                residual=residual, path=path)
    # per-layer roofline attribution (DESIGN.md §16): the XLA-fused linear
    # is the same GEMM contract the pallas kernel implements, so it records
    # under the "gemm" family with this site's path; quire-dataflow linears
    # additionally hit the codec/quire entry-point hooks downstream
    packed = "w_packed" in p
    coded = packed or "w_codes" in p
    fmt = policy.weights
    w_bytes = float(fmt.storage_bytes) if coded and fmt is not None else 4.0
    wkey = "w_packed" if packed else ("w_codes" if "w_codes" in p else "w")
    impl = ("quire" if coded and policy.dataflow == "quire"
            else "xla" if not coded else "fused")
    return prof.dispatch(
        "gemm", impl,
        prof.linear_cost(x, float(p[wkey].shape[-1]), w_bytes=w_bytes,
                         bias="b" in p, residual=residual is not None),
        lambda: _linear_resolved(p, x, policy, es, activation=activation,
                                 residual=residual, path=path),
        primary=x, path=path)


def _linear_resolved(p: dict, x: jax.Array, policy: TransPolicy, es, *,
                     activation: str, residual: Optional[jax.Array],
                     path: str) -> jax.Array:
    """apply_linear past policy resolution + observability hooks."""
    cd = _compute_dtype(policy)
    packed = "w_packed" in p
    if packed or "w_codes" in p:
        fmt = policy.weights
        assert fmt is not None, "posit-coded params need policy.weights"
        if policy.dataflow == "quire":
            return _quire_linear(p, x, policy, fmt, es, activation=activation,
                                 residual=residual, packed=packed)
        return posit_matmul_wx(
            x.astype(cd), p["w_packed"] if packed else p["w_codes"], fmt,
            es=es, compute_dtype=cd,
            bias=p.get("b"), activation=activation, residual=residual,
            codec_impl=policy.codec_impl, epilogue=policy.epilogue,
            out_dtype=x.dtype, packed=packed)
    w = effective_weight(p, policy, es, path=path).astype(cd)
    y = jnp.matmul(x.astype(cd), w, preferred_element_type=jnp.float32)
    if "b" in p or activation != "none" or residual is not None:
        y = apply_epilogue(y, p.get("b"), activation, residual,
                           chained=policy.epilogue == "chained")
    return y.astype(x.dtype)


def _quire_linear(p: dict, x: jax.Array, policy: TransPolicy, fmt: PositFmt,
                  es, *, activation: str, residual: Optional[jax.Array],
                  packed: bool) -> jax.Array:
    """dataflow="quire" lowering of a posit-coded linear (DESIGN.md §7/§9).

    Activations encode once into ``policy.activations`` (the weight format
    when unset), every product lands exactly in a Kulisch quire, and the
    single terminal rounding reads out straight into f32 for the epilogue —
    no float dot_general anywhere, which is the contract the jaxpr auditor
    (repro.analysis) asserts mechanically at quire-declared sites.
    """
    afmt = policy.activations if policy.activations is not None else fmt
    slots = OperandSlots(rs1=afmt, rs2=fmt, rd=F32, dataflow="quire",
                         codec_impl=policy.codec_impl, rs2_packed=packed)
    K = x.shape[-1]
    N = (p["w_packed"] if packed else p["w_codes"]).shape[-1]
    x2 = x.reshape(-1, K)
    res2 = None
    if residual is not None:
        res2 = jnp.broadcast_to(residual, x.shape[:-1] + (N,)).reshape(-1, N)
    a_codes = encode_with_impl(x2.astype(jnp.float32), afmt.nbits, afmt.es,
                               policy.codec_impl)
    y = posit_dot(a_codes, p["w_packed"] if packed else p["w_codes"], slots,
                  es_b=es, bias=p.get("b"), activation=activation,
                  residual=res2, epilogue=policy.epilogue)
    return y.reshape(x.shape[:-1] + (N,)).astype(x.dtype)


# linear-shaped param-dict keys quantize_params recognizes: the {"w": ...}
# convention plus MoE's stacked expert tensors (effective_weight handles
# "<name>_codes" for those; packing applies to plain linears only).
_MOE_WEIGHT_KEYS = ("w_gate", "w_up", "w_down")

# Param paths quantize_params must leave alone even though they look like
# linears: SSM causal-conv kernels are {"w", "b"} dicts consumed raw by
# _causal_conv (O(width*C) storage — not worth posit-coding anyway).
_RAW_WEIGHT_PATTERNS = ("*conv*",)


def _walk_linears(tree, path=""):
    """Yield (path, parent, key_kind) for every linear-shaped param dict."""
    if isinstance(tree, dict):
        if "w" in tree and getattr(tree["w"], "ndim", 0) >= 2:
            yield path, tree, "w"
        for k in _MOE_WEIGHT_KEYS:
            if k in tree and getattr(tree[k], "ndim", 0) >= 2:
                yield (f"{path}/{k}" if path else k), tree, k
        for k, v in tree.items():
            if k in ("w",) + _MOE_WEIGHT_KEYS:
                continue
            yield from _walk_linears(v, f"{path}/{k}" if path else k)
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _walk_linears(v, f"{path}/{i}" if path else str(i))


def quantize_params(params, policy):
    """Quantize every linear weight to its per-layer policy format.

    Walks the param tree; each linear dict {"w": ...} at path P becomes posit
    storage per ``resolve_policy(policy, P)`` — packed-p8 lanes when the
    resolved policy says ``pack_weights`` (and the contraction dim is even;
    odd dims keep unpacked codes), plain codes otherwise, untouched when the
    resolved weights format is None.  MoE expert stacks ("w_gate"/"w_up"/
    "w_down") quantize to "<name>_codes" (unpacked — the expert einsum path
    reads whole tensors).  Returns a new tree; float master params are not
    modified.
    """
    import fnmatch

    out = _copy_dicts(params)
    for path, parent, key in _walk_linears(out, ""):
        if any(fnmatch.fnmatchcase(path, pat) for pat in _RAW_WEIGHT_PATTERNS):
            continue
        pol = resolve_policy(policy, path)
        fmt = pol.weights
        if fmt is None:
            continue
        if key == "w":
            packed = (pol.pack_weights and fmt.nbits == 8
                      and parent["w"].shape[-2] % 2 == 0)
            q = quantize_linear(parent, fmt, packed=packed)
            parent.pop("w")
            parent.update(q)
        else:  # stacked MoE expert weights
            parent[key + "_codes"] = posit_encode(
                parent.pop(key).astype(jnp.float32), fmt.nbits, fmt.es)
    return out


def init_quantized_params(init_fn, key, policy):
    """``quantize_params(init_fn(key), policy)``, built one output leaf per
    jitted program.

    Each program computes only what its leaf needs, so the device never holds
    more than one float leaf (one stacked layer weight) at a time: a model
    whose f32 weights would not fit beside their posit codes still loads.
    Bit-identical to ``quantize_params(init_fn(key), policy)`` when
    ``init_fn`` is itself one jitted program (``Model.init`` is).
    """
    def build(k):
        return quantize_params(init_fn(k), policy)

    leaves, treedef = jax.tree.flatten(jax.eval_shape(build, key))
    out = [jax.jit(lambda k, i=i: jax.tree.leaves(build(k))[i])(key)
           for i in range(len(leaves))]
    return jax.tree.unflatten(treedef, out)


def _copy_dicts(tree):
    """Deep-copy the dict/list spine of a param tree (leaves shared)."""
    if isinstance(tree, dict):
        return {k: _copy_dicts(v) for k, v in tree.items()}
    if isinstance(tree, list):
        return [_copy_dicts(v) for v in tree]
    if isinstance(tree, tuple):
        return tuple(_copy_dicts(v) for v in tree)
    return tree


def policy_weight_bytes(params, policy) -> dict:
    """Storage model: linear-weight bytes at rest under ``policy`` vs f32.

    The Table-IV memory-savings number at model scale — packed p8 counts one
    byte per value (two codes per uint16 lane)."""
    import fnmatch

    f32_b = policy_b = 0
    for path, parent, key in _walk_linears(params, ""):
        w = parent[key]
        n = int(w.size)
        f32_b += 4 * n
        pol = resolve_policy(policy, path)
        fmt = pol.weights
        raw = any(fnmatch.fnmatchcase(path, pat) for pat in _RAW_WEIGHT_PATTERNS)
        policy_b += n * (fmt.storage_bytes if fmt is not None and not raw else 4)
    return {"weight_bytes_f32": f32_b, "weight_bytes_policy": policy_b}


# ------------------------------------------------------------------- norms ----

def init_rmsnorm(d: int) -> dict:
    return {"g": jnp.ones((d,), jnp.float32)}


def apply_rmsnorm(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * p["g"]).astype(x.dtype)


def init_layernorm(d: int) -> dict:
    return {"g": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def apply_layernorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]).astype(x.dtype)


# ----------------------------------------------------------------- rotary -----

def rope_freqs(head_dim: int, base: float = 10000.0) -> jax.Array:
    return 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, base: float = 10000.0) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, base)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-jnp.log(10000.0) / d))
    pe = jnp.zeros((n, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# -------------------------------------------------------------------- MLPs ----

def init_swiglu(key, d: int, f: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": init_linear(k1, d, f),
        "up": init_linear(k2, d, f),
        "down": init_linear(k3, f, d, scale=f ** -0.5),
    }


def apply_swiglu(p: dict, x: jax.Array, policy: TransPolicy, *,
                 residual: Optional[jax.Array] = None,
                 path: str = "mlp") -> jax.Array:
    """silu fuses into the gate GEMM's epilogue; an optional block residual
    fuses into the down-projection (3 fused ops per MLP instead of 6+)."""
    g = apply_linear(p["gate"], x, policy, activation="silu",
                     path=f"{path}/gate")
    u = apply_linear(p["up"], x, policy, path=f"{path}/up")
    h = g * u
    return apply_linear(p["down"], h, policy, residual=residual,
                        path=f"{path}/down")


def init_gelu_mlp(key, d: int, f: int, *, bias: bool = True) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "up": init_linear(k1, d, f, bias=bias),
        "down": init_linear(k2, f, d, bias=bias, scale=f ** -0.5),
    }


def apply_gelu_mlp(p: dict, x: jax.Array, policy: TransPolicy, *,
                   residual: Optional[jax.Array] = None,
                   path: str = "mlp") -> jax.Array:
    """gelu fuses into the up-projection epilogue; optional block residual
    fuses into the down-projection."""
    h = apply_linear(p["up"], x, policy, activation="gelu",
                     path=f"{path}/up")
    return apply_linear(p["down"], h, policy, residual=residual,
                        path=f"{path}/down")


# -------------------------------------------------------------- embeddings ----

def init_embedding(key, vocab: int, d: int) -> dict:
    return {"table": jax.random.normal(key, (vocab, d), jnp.float32) * (d ** -0.5)}


def apply_embedding(p: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0)


def embedding_logits(p: dict, h: jax.Array) -> jax.Array:
    """Tied read-out: h @ table.T."""
    return jnp.matmul(
        h.astype(jnp.float32), p["table"].T, preferred_element_type=jnp.float32)
