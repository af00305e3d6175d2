"""ServeConfig: the one typed surface for serving configuration.

serve.py grew ~30 loose argparse flags with cross-flag validation scattered
through ``main()``; hillclimb and the serving benchmarks each re-plumbed the
same engine kwargs by hand.  ``ServeConfig`` replaces that: a single
dataclass that

* round-trips as a versioned JSON document (``kind: "repro/serve-config"``,
  same header convention as ``PrecisionPolicy`` — unknown kinds, versions,
  and fields are rejected loudly, not guessed at);
* generates the CLI (:func:`add_cli_args` derives ``--flag`` names, types,
  choices, and help from the fields), so serve.py's parser cannot drift from
  the schema.  ``--config cfg.json`` loads a document and explicitly-passed
  flags override it (``argparse.SUPPRESS`` keeps unset flags out of the
  namespace entirely);
* owns the cross-field validation (:meth:`validate`) and the derived
  quantities (:meth:`s_max`);
* builds the serving objects (:meth:`build_policy`, :meth:`build_engine`) so
  serve.py, hillclimb, the benchmarks, and the HTTP server construct engines
  through one code path — the resolved config echoes in every
  ``serve/report`` line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
from typing import Optional

__all__ = ["ServeConfig", "add_cli_args", "config_from_args",
           "use_compile_cache"]

#: JAX's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: one fixed path in the checkout (the path is part of the cache key,
#: so a directory that moved between runs would never hit).
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for this process: the
    directory ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads it itself), or
    else :data:`COMPILE_CACHE_DIR`.  Call before the first compile."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))

_KIND = "repro/serve-config"
_VERSION = 1


def _f(default, help="", choices=None, cli=True):  # noqa: A002
    return dataclasses.field(default=default, metadata={
        "help": help, "choices": choices, "cli": cli})


@dataclasses.dataclass
class ServeConfig:
    """Everything a serving run needs, in one declared schema."""

    # ----- model / workload -----
    arch: str = _f(None, "architecture name (repro.configs.get_arch)")
    reduced: bool = _f(False, "use the reduced (CI-sized) config")
    batch: int = _f(4, "static batch size (and default --max-slots)")
    prompt_len: int = _f(32, "prompt length in tokens")
    gen: int = _f(16, "tokens to generate per request")
    policy: str = _f("none", "base TransPolicy spec (TransPolicy.from_spec grammar)")
    seed: int = _f(0, "PRNG seed (params, workload, sampler)")
    # ----- engine -----
    continuous: bool = _f(False, "continuous batching via launch/engine.py")
    paged: bool = _f(False, "paged prefix-sharing KV cache "
                            "(launch/paged_engine.py; implies --continuous)")
    page_bytes: int = _f(2048, "per-layer K+V bytes of one KV page "
                               "(paged mode; token capacity follows the "
                               "KV code width)")
    n_blocks: Optional[int] = _f(None, "KV pool size in blocks (paged mode; "
                                       "default: the slot grid's byte budget)")
    arrival_rate: float = _f(0.0, "Poisson arrival rate req/s (0 = all at t=0)")
    max_slots: Optional[int] = _f(None, "decode slot grid size (default: "
                                        "--batch)")
    requests: Optional[int] = _f(None, "requests to serve (default: 2*slots)")
    temperature: float = _f(0.0, "0 = greedy; >0 samples (with --top-k)")
    top_k: int = _f(0, "top-k truncation for sampling")
    deadline_s: Optional[float] = _f(None, "per-request wall-clock budget "
                                           "from arrival (finish_reason="
                                           "timeout past it)")
    # ----- precision -----
    precision_policy: Optional[str] = _f(
        None, "per-layer weight schedule: preset, pattern=fmt[@es][:packed] "
              "spec, or @artifact.json (core/policy.py)")
    calibrate: int = _f(0, "run N calibration passes and serve under the "
                           "searched dynamic-es policy (DESIGN.md §11)")
    policy_out: Optional[str] = _f(None, "write the calibration artifact "
                                         "JSON here")
    weight_byte_budget: Optional[str] = _f(
        None, "calibration byte budget: absolute bytes or '<mult>x' the "
              "p8 floor")
    quantize_weights: bool = _f(False, "store weights as real posit codes "
                                       "under the schedule")
    codec_impl: str = _f("auto", "codec lowering", choices=("auto", "lut",
                                                            "bits"))
    epilogue: str = _f("fused", "layer dataflow", choices=("fused", "chained"))
    attn_impl: str = _f("auto", "decode attention dispatch",
                        choices=("auto", "kernel", "xla"))
    # ----- observability -----
    metrics_out: Optional[str] = _f(None, "metrics snapshot JSON path "
                                          "(+ <path>.prom exposition)")
    trace_out: Optional[str] = _f(None, "Chrome-trace/Perfetto timeline path")
    profile_out: Optional[str] = _f(None, "per-kernel roofline-attribution "
                                          "report path (JSON + .md)")
    numerics_watch: int = _f(0, "probe every N-th decode step for posit "
                                "saturation/underflow/NaR and drift")
    # ----- fault tolerance -----
    snapshot_every: int = _f(0, "crash-safe engine snapshot every N steps")
    snapshot_dir: Optional[str] = _f(None, "checkpoint directory for "
                                          "snapshots / --resume")
    resume: bool = _f(False, "restore the newest snapshot and continue")
    degrade: bool = _f(False, "numerics-driven precision degradation ladder")
    chaos_preempt_step: Optional[int] = _f(None, "fault injection: SIGTERM "
                                                 "at decode step N")
    # ----- request plane (launch/server.py) -----
    host: str = _f("127.0.0.1", "HTTP server bind address")
    port: int = _f(8100, "HTTP server port")
    max_queue: int = _f(64, "admission queue bound; beyond it requests get "
                            "429 (backpressure)")

    # ------------------------------------------------------------- schema ----
    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        return {"kind": _KIND, "version": _VERSION, **d}

    @classmethod
    def from_json(cls, d: dict) -> "ServeConfig":
        if d.get("kind") != _KIND:
            raise ValueError(f"not a serve-config document: kind="
                             f"{d.get('kind')!r} (want {_KIND!r})")
        if int(d.get("version", 1)) != _VERSION:
            raise ValueError(
                f"serve-config v{d.get('version')} is not v{_VERSION}; "
                f"refusing to guess at an unknown schema")
        body = {k: v for k, v in d.items() if k not in ("kind", "version")}
        known = {f.name for f in dataclasses.fields(cls)}
        bad = set(body) - known
        if bad:
            raise ValueError(f"unknown serve-config fields {sorted(bad)} "
                             f"(hand-edited document? schema is v{_VERSION})")
        return cls(**body)

    @classmethod
    def load(cls, path: str) -> "ServeConfig":
        with open(path) as f:
            return cls.from_json(json.load(f))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1)

    # --------------------------------------------------------- validation ----
    def validate(self) -> "ServeConfig":
        """Cross-field checks (raises ValueError with a CLI-ready message)."""
        if not self.arch:
            raise ValueError("--arch is required (or 'arch' in --config)")
        if self.paged and not self.continuous:
            raise ValueError("--paged rides the continuous-batching engine; "
                             "add --continuous")
        if not self.calibrate and (self.policy_out or self.weight_byte_budget):
            raise ValueError(
                "--policy-out / --weight-byte-budget require --calibrate N "
                "(they configure the calibration search; a loaded "
                "--precision-policy artifact is served as saved)")
        if not self.continuous and (self.trace_out or self.numerics_watch):
            raise ValueError(
                "--trace-out / --numerics-watch instrument the continuous-"
                "batching engine; add --continuous")
        if (self.snapshot_every or self.resume) and not self.snapshot_dir:
            raise ValueError("--snapshot-every / --resume need --snapshot-dir")
        if self.resume and not self.snapshot_every:
            raise ValueError("--resume needs --snapshot-every N (the resumed "
                             "run keeps snapshotting)")
        if self.snapshot_every and not self.continuous:
            raise ValueError("--snapshot-every snapshots the continuous-"
                             "batching engine; add --continuous")
        if self.degrade and not self.numerics_watch:
            raise ValueError("--degrade consumes the numerics watcher's "
                             "health rows; add --numerics-watch N")
        if self.chaos_preempt_step is not None and not self.snapshot_every:
            raise ValueError("--chaos-preempt-step kills a snapshotting run; "
                             "add --snapshot-every N (and --snapshot-dir)")
        if self.deadline_s is not None and not self.continuous:
            raise ValueError("--deadline-s is enforced by the continuous-"
                             "batching engine; add --continuous")
        return self

    # ------------------------------------------------------------ builders ---
    def arch_cfg(self):
        from repro.configs import get_arch
        cfg = get_arch(self.arch)
        return cfg.reduced() if self.reduced else cfg

    def s_max(self, cfg) -> int:
        """Cache rows per slot: prompt + generation budget, plus the patch
        prefix for vlm rows (it lives in the same cache)."""
        return self.prompt_len + self.gen + \
            (cfg.n_patches if cfg.family == "vlm" else 0)

    def build_policy(self, base=None):
        """(TransPolicy-or-PrecisionPolicy, drift_meta) from the precision
        fields — the one resolution path serve.py / hillclimb / benches use.
        ``base`` overrides the ``policy`` spec with an already-built
        TransPolicy (hillclimb's variant table hands these in directly)."""
        from repro.core.pcsr import TransPolicy
        from repro.core.policy import get_precision_policy
        policy = dataclasses.replace(
            base if base is not None else TransPolicy.from_spec(self.policy),
            codec_impl=self.codec_impl, epilogue=self.epilogue,
            attn_impl=self.attn_impl)
        drift_meta = None
        if self.precision_policy:
            policy = get_precision_policy(self.precision_policy, base=policy)
            if self.precision_policy.startswith("@"):
                with open(self.precision_policy[1:]) as f:
                    drift_meta = json.load(f)
        return policy, drift_meta

    def load_model(self, policy):
        """(model, params) for ``arch`` with weights made from ``seed``.

        Under ``quantize_weights`` the weights are posit-coded under
        ``policy`` as they are made, one leaf at a time
        (``models.layers.init_quantized_params``), so a model whose f32
        weights exceed device memory still loads.  A ``calibrate`` run gets
        float weights: the calibration must see them before it picks the
        policy that codes them."""
        import jax

        from repro.models.layers import init_quantized_params
        from repro.models.registry import build_model

        model = build_model(self.arch_cfg())
        key = jax.random.key(self.seed)
        if self.quantize_weights and not self.calibrate:
            return model, init_quantized_params(model.init, key, policy)
        return model, model.init(key)

    def build_engine(self, model, params, policy, **sinks):
        """Construct the serving engine this config describes.

        ``sinks`` forwards the observability / ft keywords
        (``metrics=``, ``tracer=``, ``numerics=``, ``snapshotter=``,
        ``watchdog=``, ``faults=``, ``prefill_kwargs=``, ...).
        """
        from repro.launch.engine import ContinuousBatchingEngine
        common = dict(max_slots=self.max_slots or self.batch,
                      S_max=self.s_max(model.cfg),
                      temperature=self.temperature, top_k=self.top_k,
                      seed=self.seed, deadline_s=self.deadline_s, **sinks)
        if self.paged:
            from repro.launch.paged_engine import PagedContinuousBatchingEngine
            return PagedContinuousBatchingEngine(
                model, params, policy, page_bytes=self.page_bytes,
                n_blocks=self.n_blocks, **common)
        return ContinuousBatchingEngine(model, params, policy, **common)


# ------------------------------------------------------------------- CLI ----

def add_cli_args(ap: argparse.ArgumentParser) -> None:
    """Derive the serve CLI from the ServeConfig schema (one flag per field;
    bools are ``store_true``).  Defaults are ``argparse.SUPPRESS`` so
    :func:`config_from_args` can tell "flag passed" from "flag at default"
    and layer overrides on a ``--config`` document."""
    for f in dataclasses.fields(ServeConfig):
        if not f.metadata.get("cli", True):
            continue
        flag = "--" + f.name.replace("_", "-")
        help_ = f.metadata.get("help", "")
        choices = f.metadata.get("choices")
        if f.type in ("bool", bool):
            ap.add_argument(flag, action="store_true",
                            default=argparse.SUPPRESS, help=help_)
            continue
        typ = {"int": int, "float": float, "str": str,
               "Optional[int]": int, "Optional[float]": float,
               "Optional[str]": str}.get(
                   f.type if isinstance(f.type, str) else f.type.__name__,
                   str)
        ap.add_argument(flag, type=typ, choices=choices,
                        default=argparse.SUPPRESS, help=help_)


def config_from_args(args: argparse.Namespace,
                     base: Optional[ServeConfig] = None) -> ServeConfig:
    """Layer explicitly-passed flags over ``base`` (a ``--config`` document)
    or the schema defaults."""
    cfg = base if base is not None else ServeConfig()
    known = {f.name for f in dataclasses.fields(ServeConfig)}
    overrides = {k: v for k, v in vars(args).items() if k in known}
    return dataclasses.replace(cfg, **overrides)
