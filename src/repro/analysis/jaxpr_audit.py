"""Jaxpr numerics auditor — hazards JP001-JP006 (DESIGN.md §15).

The linter checks what the *source* says; this module checks what the traced
program actually *does*.  ``audit_model`` traces ``model.loss`` (float
params, calibration markers installed) and ``model.decode_step`` (posit-
quantized params, the serving executable) for a registry family under a
given policy, then walks the ClosedJaxpr:

* **JP001** — a posit *code* tensor (uint8/uint16 storage) flows into value
  arithmetic (``add``/``mul``/``dot_general``/reductions) without passing
  through a decode.  Codes are an opaque bit domain: the only legal exits
  are bitwise field extraction (decode), gather indexing (LUT decode) and
  equality tests (NaR checks).  Taint analysis: code-dtype inputs seed,
  transport ops propagate, bitwise ops *kill* (that is the decode boundary),
  arithmetic on a tainted operand is the finding.
* **JP002** — a site whose resolved policy declares ``dataflow="quire"``
  still lowers to a float ``dot_general`` (``audit_quire_sites``): the
  exact-accumulation contract silently degraded to FPU accumulate, e.g.
  because the params were never quantized or a code path bypassed
  ``_quire_linear``.
* **JP003** — encode->decode round-trip churn: a decode whose codes came
  straight from an encode in the same executable with no storage boundary
  (KV-cache writes, checkpoint slices) in between — two codec passes where
  a no-op would do.  The training-path straight-through estimator is the
  deliberate exception (its decode output feeds the ``sub`` of
  ``w + stop_grad(qw - wf)``) and is exempted structurally.
* **JP004** — ``convert_element_type`` narrowing f32 -> bf16/f16 feeding a
  reduction (``reduce_sum``/``dot_general``) that *accumulates in the
  narrow dtype* within a few transport hops.  Narrow inputs with an f32
  accumulator (``preferred_element_type``) are the sanctioned pattern and
  do not fire.
* **JP005** — ``debug_callback`` equations baked into the non-probed
  serving executable: a forgotten observer hook re-traces into every decode
  step and stalls the drive loop on host syncs (the §12 probes install
  observers *cadenced*, never in the steady-state executable).
* **JP006** — dead ``PrecisionPolicy`` rules: a non-catchall rule matching
  no linear path in the model (typo'd pattern — the layer it meant to
  schedule silently runs at the base format).  One dead rule is a warning
  (presets legitimately carry rules only some families match); *all*
  non-catchall rules dead is an error.

Findings carry ``arch:trace/layer-path`` locations — the layer path is
recovered from the calibration observer's ``debug_callback`` markers
(``(path, kind)`` keys, the same keying ``calib.observe`` streams stats
under), so a hazard inside a scanned block names the call site that
produced it.
"""
from __future__ import annotations

import fnmatch
import functools
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.base import Finding
from repro.calib.observe import Observer, observing
from repro.calib.search import calibration_batches
from repro.configs import ARCH_IDS, get_arch
from repro.models.layers import (_RAW_WEIGHT_PATTERNS, _walk_linears,
                                 apply_linear, quantize_params, resolve_policy)
from repro.models.registry import build_model

# One representative per registry family — the CLI's default audit matrix
# (nightly CI runs the full ARCH_IDS cross product).
DEFAULT_AUDIT_ARCHS = (
    "phi3-mini-3.8b",     # dense
    "olmoe-1b-7b",        # moe
    "gemma3-4b",          # gemma3 local/global
    "zamba2-7b",          # ssm hybrid
    "xlstm-125m",         # xlstm
    "whisper-medium",     # encoder-decoder
    "internvl2-2b",       # vlm
)

# Posit code storage dtypes: the taint domain of JP001.
_CODE_DTYPES = (jnp.uint8, jnp.uint16)

# Value-preserving data movement: taint flows through.
_TRANSPORT = frozenset({
    "reshape", "broadcast_in_dim", "transpose", "squeeze", "rev", "copy",
    "slice", "concatenate", "pad", "dynamic_slice", "dynamic_update_slice",
    "gather", "select_n", "scatter", "scatter-add",
})
# Bit-domain ops: field extraction, i.e. the decode boundary — outputs leave
# the code domain.
_BITWISE = frozenset({
    "and", "or", "xor", "not", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "population_count", "clz",
})
# Value arithmetic: a tainted operand here is the JP001 hazard.
_ARITH = frozenset({
    "add", "sub", "mul", "div", "rem", "pow", "integer_pow", "dot_general",
    "reduce_sum", "reduce_prod", "reduce_max", "reduce_min", "max", "min",
    "exp", "log", "tanh", "logistic", "cumsum",
})
# Storage boundaries that break a JP003 encode->decode chain: codes that
# were *stored* (cache writes/reads, slices of a persisted buffer) are
# decoded legitimately.
_STORAGE = frozenset({
    "dynamic_update_slice", "dynamic_slice", "slice", "gather", "scatter",
    "scatter-add", "concatenate", "pad",
})
_NARROW = (jnp.bfloat16, jnp.float16)


def _is_code(v) -> bool:
    dt = getattr(getattr(v, "aval", None), "dtype", None)
    return dt is not None and any(dt == d for d in _CODE_DTYPES)


def _dtype(v):
    return getattr(getattr(v, "aval", None), "dtype", None)


def _sub_jaxprs(eqn):
    """Every sub-jaxpr an equation closes over (jit/scan/while/cond/...)."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, jax.extend.core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jax.extend.core.Jaxpr):
                yield v


def _marker_key(eqn) -> Optional[Tuple[str, str]]:
    """Recover the observer's ``(path, kind)`` key from a debug_callback eqn.

    ``calib.observe.Observer.record`` ships stats through
    ``jax.debug.callback(functools.partial(self._accum, (path, kind), ...))``
    — the key is the partial's first positional arg, however many wrapper
    layers jax's callback machinery adds around it.  Best-effort: returns
    None when no key is found (finding paths then fall back to the trace
    name).
    """
    return _find_key(eqn.params.get("callback"), 0)


def _find_key(obj, depth: int) -> Optional[Tuple[str, str]]:
    if depth > 6 or obj is None:
        return None
    if isinstance(obj, functools.partial):
        for a in obj.args:
            if (isinstance(a, tuple) and len(a) == 2
                    and all(isinstance(s, str) for s in a)
                    and a[1] in ("weight", "act", "grad")):
                return a
        for sub in (obj.func, *obj.args, *obj.keywords.values()):
            k = _find_key(sub, depth + 1)
            if k is not None:
                return k
        return None
    if callable(obj):
        for cell in getattr(obj, "__closure__", None) or ():
            try:
                k = _find_key(cell.cell_contents, depth + 1)
            except ValueError:
                continue
            if k is not None:
                return k
        wrapped = getattr(obj, "__wrapped__", None)
        if wrapped is not None and wrapped is not obj:
            return _find_key(wrapped, depth + 1)
    return None


# ------------------------------------------------------------------ walker ----

class _Audit:
    def __init__(self, trace: str, probed: bool):
        self.trace = trace
        self.probed = probed
        self.findings: List[Finding] = []
        self.marker: Optional[str] = None  # last observer path seen in order

    def _loc(self) -> str:
        return f"{self.trace}/{self.marker}" if self.marker else self.trace

    def add(self, rule: str, message: str, snippet: str,
            severity: str = "error") -> None:
        self.findings.append(Finding(
            rule=rule, path=self._loc(), message=message, snippet=snippet,
            severity=severity))

    # -- one jaxpr (recursing into sub-jaxprs; each seeds its own taint) ----
    def walk(self, jaxpr) -> None:
        tainted: Set = {v for v in (*jaxpr.invars, *jaxpr.constvars)
                        if _is_code(v)}
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "debug_callback":
                key = _marker_key(eqn)
                if key is not None:
                    self.marker = key[0]
                if not self.probed:
                    self.add(
                        "JP005",
                        "debug_callback baked into a non-probed steady-state "
                        "executable (serving decode or plain train step): "
                        "every step pays a host sync (observers belong on "
                        "the cadenced probe/telemetry-twin executables, "
                        "DESIGN.md §12/§16)",
                        snippet="debug_callback")
                continue
            for sub in _sub_jaxprs(eqn):
                self.walk(sub)
            self._step_taint(eqn, tainted)
        self._churn(jaxpr)
        self._narrowed_reductions(jaxpr)

    # -- JP001 taint propagation -------------------------------------------
    def _step_taint(self, eqn, tainted: Set) -> None:
        name = eqn.primitive.name
        invars = [v for v in eqn.invars if not isinstance(v, jax.extend.core.Literal)]

        def hot(vs) -> bool:
            return any(v in tainted for v in vs)

        if name in _BITWISE:
            return  # field extraction: the decode boundary kills taint
        if name == "convert_element_type":
            out = eqn.outvars[0]
            if hot(invars):
                tainted.add(out)
            elif (_dtype(out) is not None
                  and any(_dtype(out) == d for d in _CODE_DTYPES)
                  and invars and np.issubdtype(_dtype(invars[0]), np.integer)):
                tainted.add(out)  # encode tail: wide int -> code storage
            return
        if name in _TRANSPORT:
            # index-consuming ops: taint rides the *data* operand only — a
            # gather indexed by codes (LUT decode) produces clean values
            if name in ("gather", "dynamic_slice"):
                src = hot(invars[:1])
            elif name in ("dynamic_update_slice", "scatter", "scatter-add"):
                src = hot(invars[:1]) or hot(invars[-1:])
            elif name == "select_n":
                src = hot(invars[1:])
            else:
                src = hot(invars)
            if src:
                tainted.update(eqn.outvars)
            return
        if name in _ARITH and hot(invars):
            culprits = sorted({str(_dtype(v)) for v in invars
                               if v in tainted})
            self.add(
                "JP001",
                f"posit code tensor ({', '.join(culprits)}) used as a value "
                f"operand of `{name}` without decode — codes are an opaque "
                f"bit domain; arithmetic on them is numerically meaningless",
                snippet=f"{name}({', '.join(str(_dtype(v)) for v in eqn.invars)})")
            return
        # comparisons (NaR checks) and everything else: outputs leave taint

    # -- JP003 encode->decode churn ----------------------------------------
    def _churn(self, jaxpr) -> None:
        prod = {v: eqn for eqn in jaxpr.eqns for v in eqn.outvars}
        consumers: Dict = {}
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if not isinstance(v, jax.extend.core.Literal):
                    consumers.setdefault(v, []).append(eqn)
        encode_tails = set()
        for eqn in jaxpr.eqns:
            if (eqn.primitive.name == "convert_element_type"
                    and any(_dtype(eqn.outvars[0]) == d for d in _CODE_DTYPES)
                    and np.issubdtype(_dtype(eqn.invars[0]), np.integer)):
                encode_tails.update(eqn.outvars)

        if not encode_tails:
            return

        for eqn in jaxpr.eqns:
            head = None  # the integer codes var this decode consumes
            if (eqn.primitive.name == "bitcast_convert_type"
                    and np.issubdtype(_dtype(eqn.invars[0]), np.integer)
                    and np.issubdtype(_dtype(eqn.outvars[0]), np.floating)):
                head = eqn.invars[0]
            elif (eqn.primitive.name == "gather" and len(eqn.invars) >= 2
                    and np.issubdtype(_dtype(eqn.invars[0]), np.floating)
                    and np.issubdtype(_dtype(eqn.invars[1]), np.integer)):
                head = eqn.invars[1]  # LUT decode: float table, code index
            if head is None or isinstance(head, jax.extend.core.Literal):
                continue
            if not self._reaches_encode(head, prod, encode_tails):
                continue
            if self._is_ste(eqn.outvars[0], consumers):
                continue
            self.add(
                "JP003",
                "encode->decode round trip with no storage boundary in "
                "between: two codec passes where the value was already in "
                "hand (the training-path straight-through estimator is the "
                "exempted exception)",
                snippet=f"churn:{eqn.primitive.name}")

    @staticmethod
    def _reaches_encode(var, prod, encode_tails, limit: int = 400) -> bool:
        """Backward BFS from a decode's code operand through in-register int
        ops; storage ops break the chain (stored codes decode legitimately)."""
        seen = set()
        frontier = [var]
        while frontier and len(seen) < limit:
            v = frontier.pop()
            if v in seen or isinstance(v, jax.extend.core.Literal):
                continue
            seen.add(v)
            if v in encode_tails:
                return True
            eqn = prod.get(v)
            if eqn is None or eqn.primitive.name in _STORAGE:
                continue
            if eqn.primitive.name in (_BITWISE | {
                    "convert_element_type", "reshape", "broadcast_in_dim",
                    "transpose", "squeeze", "rev", "copy", "select_n",
                    "add", "sub", "mul"}):
                frontier.extend(u for u in eqn.invars
                                if not isinstance(u, jax.extend.core.Literal))
        return False

    # decode epilogues between the bitcast/LUT readout and the value proper:
    # NaR select, sign application, dtype casts.  The STE search follows
    # these (and nothing else) forward to find the `qw - wf` sub.
    _DECODE_EPILOGUE = frozenset({
        "convert_element_type", "select_n", "mul", "neg", "reshape",
        "broadcast_in_dim", "transpose", "squeeze", "copy",
        "jit",  # jnp.where wraps its select in a jit — pass through it
    })

    @classmethod
    def _is_ste(cls, out, consumers, limit: int = 24) -> bool:
        """Straight-through-estimator shape: the decode output (through the
        decode's own epilogue ops) is an operand of a ``sub`` (the
        ``qw - wf`` of ``effective_weight``)."""
        seen = set()
        frontier = [out]
        while frontier and len(seen) < limit:
            v = frontier.pop()
            if v in seen:
                continue
            seen.add(v)
            for eqn in consumers.get(v, ()):
                if eqn.primitive.name == "sub":
                    return True
                if eqn.primitive.name in cls._DECODE_EPILOGUE:
                    frontier.extend(eqn.outvars)
        return False

    # -- JP004 narrowing upstream of a reduction ---------------------------
    def _narrowed_reductions(self, jaxpr) -> None:
        consumers: Dict = {}
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if not isinstance(v, jax.extend.core.Literal):
                    consumers.setdefault(v, []).append(eqn)
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            src, out = _dtype(eqn.invars[0]), _dtype(eqn.outvars[0])
            if src != jnp.float32 or not any(out == d for d in _NARROW):
                continue
            frontier = [eqn.outvars[0]]
            for _ in range(3):
                nxt = []
                for v in frontier:
                    for c in consumers.get(v, ()):
                        cn = c.primitive.name
                        if cn in ("reduce_sum", "dot_general") and any(
                                _dtype(c.outvars[0]) == d for d in _NARROW):
                            self.add(
                                "JP004",
                                f"f32 narrowed to {out} and then accumulated "
                                f"in {_dtype(c.outvars[0])} by `{cn}` — "
                                f"narrow inputs are fine, narrow "
                                f"*accumulators* lose the paper's error "
                                f"budget (use preferred_element_type=f32)",
                                snippet=f"narrow:{cn}:{out}")
                            return
                        if cn in _TRANSPORT or cn == "convert_element_type":
                            nxt.extend(c.outvars)
                frontier = nxt
                if not frontier:
                    break


def audit_closed_jaxpr(closed, *, trace: str = "trace",
                       probed: bool = False) -> List[Finding]:
    """Walk one traced executable for JP001/JP003/JP004/JP005.

    ``probed=True`` marks an executable that is *supposed* to carry observer
    callbacks (a calibration or probe trace): JP005 is silenced and the
    callbacks' ``(path, kind)`` keys attribute findings to layer paths.
    """
    a = _Audit(trace, probed)
    a.walk(closed.jaxpr)
    # scans/vmaps replay one body many times; identical findings collapse
    seen, out = set(), []
    for f in a.findings:
        fp = f.fingerprint()
        if fp not in seen:
            seen.add(fp)
            out.append(f)
    return out


# ------------------------------------------------------- JP002 quire sites ----

def _site_params(tree, path: str) -> dict:
    """The (possibly quantized) param dict at a _walk_linears path, with
    scan-stacked leading layer axes sliced off so the dict traces as one
    layer's linear."""
    node = tree
    for seg in path.split("/"):
        if seg:
            node = node[int(seg)] if isinstance(node, (list, tuple)) else node[seg]
    out = {}
    for k, v in node.items():
        if k in ("w", "w_codes", "w_packed") and getattr(v, "ndim", 0) == 3:
            v = v[0]
        elif k == "b" and getattr(v, "ndim", 0) == 2:
            v = v[0]
        out[k] = v
    return out


def _has_float_dot(jaxpr) -> bool:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                np.issubdtype(_dtype(v), np.floating) for v in eqn.invars):
            return True
        for sub in _sub_jaxprs(eqn):
            if _has_float_dot(sub):
                return True
    return False


def audit_quire_sites(arch_or_cfg, policy, *, params=None,
                      quantize: bool = True) -> Tuple[List[Finding], int]:
    """JP002: verify every quire-declared linear lowers to quire dataflow.

    Walks the model's linears; for each site whose *resolved* policy says
    ``dataflow="quire"`` with a posit weight format, traces ``apply_linear``
    on that site's (quantized) params and flags any float ``dot_general`` in
    the result — the quire path is pure integer accumulation with one
    terminal rounding, so a float contraction means the exact-accumulation
    contract silently degraded.  ``quantize=False`` audits the float tree
    (the CI seeded-violation fixture: unquantized params at quire sites
    *must* fire).  Returns ``(findings, n_quire_sites)``.
    """
    cfg = get_arch(arch_or_cfg).reduced() if isinstance(arch_or_cfg, str) \
        else arch_or_cfg
    model = build_model(cfg)
    if params is None:
        params = model.init(jax.random.PRNGKey(0))
    tree = quantize_params(params, policy) if quantize else params

    findings: List[Finding] = []
    n_sites = 0
    for path, parent, key in _walk_linears(params, ""):
        if key != "w":
            continue  # MoE expert einsums stay on the fused FPU datapath
        if any(fnmatch.fnmatchcase(path, pat) for pat in _RAW_WEIGHT_PATTERNS):
            continue
        pol = resolve_policy(policy, path)
        if pol.dataflow != "quire" or pol.weights is None:
            continue
        n_sites += 1
        site = _site_params(tree, path)
        d_in = parent["w"].shape[-2]
        x = jax.ShapeDtypeStruct((2, d_in), jnp.float32)
        closed = jax.make_jaxpr(
            lambda pd, xx, _path=path: apply_linear(pd, xx, policy, path=_path)
        )(site, x)
        if _has_float_dot(closed.jaxpr):
            findings.append(Finding(
                rule="JP002",
                path=f"{cfg.name}:{path}",
                message=(
                    "quire-declared site lowers to a float dot_general: the "
                    "exact-accumulation contract degraded to FPU accumulate "
                    "(params not quantized, or the site bypassed "
                    "_quire_linear)"),
                snippet="quire-site:float-dot"))
    return findings, n_sites


# ---------------------------------------------------------- JP006 dead rules --

def dead_rules(policy, params, *, arch: str = "model") -> List[Finding]:
    """Non-catchall PrecisionPolicy rules that win for no linear path."""
    rules = getattr(policy, "rules", None)
    if not rules:
        return []
    paths = [p for p, _, _ in _walk_linears(params, "")]
    live = set()
    for p in paths:
        r = policy.rule_for(p)
        if r is not None:
            live.add(id(r))
    dead = [r for r in rules if r.pattern != "*" and id(r) not in live]
    non_catchall = [r for r in rules if r.pattern != "*"]
    if not dead:
        return []
    if len(dead) == len(non_catchall):
        return [Finding(
            rule="JP006", path=f"{arch}:policy",
            message=(
                f"every non-catchall precision rule is dead "
                f"({', '.join(r.pattern for r in dead)} match no linear "
                f"path): the schedule is a no-op and the whole model runs "
                f"at the base/catch-all format"),
            snippet="dead:all")]
    return [Finding(
        rule="JP006", path=f"{arch}:policy",
        message=(f"precision rule {r.pattern!r} matches no linear path in "
                 f"this model (typo, or a family without that block)"),
        snippet=f"dead:{r.pattern}", severity="warn") for r in dead]


# ------------------------------------------------------ training executables --

def trace_train_step(arch_or_cfg, policy, *, seq: int = 16,
                     telemetry: bool = False, observed: bool = False):
    """Trace one training executable (``make_train_step``) to a ClosedJaxpr.

    ``telemetry`` selects the probed-twin builder (extra params-sized metric
    reductions); ``observed`` traces under a three-channel observer
    (weight/act/grad) so the §11/§16 callbacks — including the ``grad_tap``
    cotangent hooks — bake into the executable.  The four combinations are
    the JP005 truth table for the training plane (see ``audit_train``).
    """
    from repro.launch.steps import make_train_step
    from repro.optim import AdamWConfig, adamw_init

    cfg = get_arch(arch_or_cfg).reduced() if isinstance(arch_or_cfg, str) \
        else arch_or_cfg
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt_cfg = AdamWConfig(lr=1e-3,
                          moment_fmt=getattr(policy, "optimizer", None))
    opt = adamw_init(params, opt_cfg)
    batch = calibration_batches(
        cfg, np.random.default_rng(0), 1, batch=2, seq=seq)[0]
    step = make_train_step(model, policy, opt_cfg, warmup=1, total_steps=4,
                           telemetry=telemetry)

    def tr(p, o, b):
        return step(p, o, b, jnp.int32(0))

    if observed:
        obs = Observer(kinds=("weight", "act", "grad"))
        with observing(obs):
            return jax.make_jaxpr(tr)(params, opt, batch)
    return jax.make_jaxpr(tr)(params, opt, batch)


def audit_train(arch: str, policy, *, seq: int = 16) -> List[Finding]:
    """JP005 for the training plane (plus JP001/3/4 over both executables).

    The §16 probed-twin contract: the *plain* train step — the executable
    every non-probed step runs — must carry zero ``debug_callback`` host
    syncs, while the telemetry twin (traced under the observer, grad taps
    live) is exempt exactly like the §12 probe trace.  A leaked observer
    context around the plain step's trace is the seeded positive — it bakes
    the callbacks in and fires.
    """
    findings = audit_closed_jaxpr(
        trace_train_step(arch, policy, seq=seq),
        trace=f"{arch}:train", probed=False)
    findings += audit_closed_jaxpr(
        trace_train_step(arch, policy, seq=seq, telemetry=True,
                         observed=True),
        trace=f"{arch}:train-probed", probed=True)
    return findings


# -------------------------------------------------------------- audit_model ---

def audit_model(arch: str, policy, *, seq: int = 16,
                s_max: int = 32) -> List[Finding]:
    """Trace + audit one registry family under ``policy``.

    Three trace groups: ``loss`` (float params, observer markers installed —
    the calibration executable, JP005-exempt), ``decode`` (posit-quantized
    params, the steady-state serving executable, where a debug_callback is a
    real JP005 hazard), and the training pair from :func:`audit_train` (the
    plain train step is JP005-gated like decode; the telemetry twin is
    exempt).  Adds the JP002 quire-contract sweep when any site resolves to
    quire dataflow, and the JP006 dead-rule scan for PrecisionPolicy
    schedules.
    """
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = calibration_batches(
        cfg, np.random.default_rng(0), 1, batch=2, seq=seq)[0]

    findings: List[Finding] = []

    obs = Observer()
    with observing(obs):
        closed_loss = jax.make_jaxpr(
            lambda p, b: model.loss(p, b, policy))(params, batch)
    findings += audit_closed_jaxpr(
        closed_loss, trace=f"{arch}:loss", probed=True)

    qp = quantize_params(params, policy)
    if cfg.family == "whisper":
        cache = jax.eval_shape(
            lambda p: model.init_cache(p, batch, policy, s_max), qp)
    else:
        cache = jax.eval_shape(lambda: model.init_cache(2, s_max, policy))
    qshapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), qp)
    tok = jax.ShapeDtypeStruct((2,), jnp.int32)
    closed_dec = jax.make_jaxpr(
        lambda p, t, c: model.decode_step(p, t, c, policy))(qshapes, tok, cache)
    findings += audit_closed_jaxpr(
        closed_dec, trace=f"{arch}:decode", probed=False)

    findings += audit_train(arch, policy, seq=seq)

    if any(resolve_policy(policy, p).dataflow == "quire"
           for p, _, k in _walk_linears(params, "") if k == "w"):
        qf, _ = audit_quire_sites(cfg, policy, params=params)
        findings += qf

    findings += dead_rules(policy, params, arch=arch)
    return findings


def audit_archs(archs: Sequence[str], policy) -> List[Finding]:
    out: List[Finding] = []
    for a in (ARCH_IDS if archs == ["all"] else archs):
        out.extend(audit_model(a, policy))
    return out
