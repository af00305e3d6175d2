"""PCSR — the framework analogue of the paper's posit control & status register.

The hardware pcsr (paper Fig. 2(c)) holds, for three input operand slots and one
output slot:
    pfmt  (1 bit)  — posit vs IEEE float (bypass the codec entirely)
    pprec (1 bit)  — 8- vs 16-bit posit
    pes   (3 bits) — exponent size

Here the same runtime knobs are carried as a policy object. Two layers:

* ``OperandSlots`` — the literal pcsr: formats for (rs1, rs2, rs3, rd) of a
  single op. Used by ``repro.core.dot`` for mixed-format GEMMs.
* ``TransPolicy`` — the systems-level extension: which format each *tensor
  role* in a model uses (weights / activations / gradients / KV cache /
  optimizer moments / collectives / checkpoint). This is what a training or
  serving run is configured with.

``es`` values are kept as plain ints here; ops lower them as traced scalars so
changing es at runtime does not retrace (DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core.types import F32, Fmt, PositFmt, get_format


# Accumulation dataflows a dot-like op can run under (repro.core.dot):
#   fused    — decode inside the matmul, f32 FPU accumulation (the paper)
#   unfused  — [7]-style separate conversion passes, same numerics as fused
#   quire    — PERCIVAL-style exact Kulisch accumulation, single terminal
#              rounding (repro.core.quire / kernels.posit_quire_gemm)
DATAFLOWS = ("fused", "unfused", "quire")

# Codec implementations (repro.core.lut): "bits" is the ~40-op integer
# pipeline (the only option inside Mosaic kernel bodies), "lut" the
# table/gather fast path, "auto" picks per backend.
CODEC_IMPLS = ("auto", "lut", "bits")

# Epilogue dataflows for dot-like ops (repro.core.dot): "fused" keeps
# bias/activation/residual/encode in the producing kernel (one HBM write);
# "chained" materializes each stage — the [7]-style round-trip baseline.
EPILOGUES = ("fused", "chained")

# Decode-step attention implementations (models.attention /
# kernels.posit_attention.ops): "kernel" routes each step through the
# flash-decode front door (Pallas on TPU, length-bounded tiled XLA path
# elsewhere — the cache is decoded tile-wise at the attention boundary, never
# materialized in full); "xla" is the in-model full-cache decode + dense
# einsum baseline; "auto" resolves to "kernel" wherever the kernel contract
# covers the layer (everything except non-rolling sliding-window caches).
ATTN_IMPLS = ("auto", "kernel", "xla")

# Accumulation dataflows a whole-run policy can declare for its linear
# layers (models.layers.apply_linear).  A subset of DATAFLOWS: "unfused" is
# a benchmark baseline, not a policy anyone serves under.  "quire" routes
# every posit-coded linear through the exact Kulisch accumulator — no float
# dot_general at declared sites, one terminal rounding into the FPU domain —
# and is what repro.analysis's jaxpr auditor verifies mechanically.
POLICY_DATAFLOWS = ("fused", "quire")


@dataclasses.dataclass(frozen=True)
class OperandSlots:
    """Per-op format config: 3 input slots + 1 output slot (the literal pcsr).

    ``dataflow`` is the beyond-paper pcsr bit pair selecting the accumulation
    path; it is a *static* field (it changes the lowered program, unlike es
    which stays a traced scalar).  ``codec_impl`` selects the codec
    implementation the op's decodes/encodes lower to (also static).
    """

    rs1: Fmt = F32
    rs2: Fmt = F32
    rs3: Fmt = F32  # fused-op third operand (e.g. addend of FMA / bias)
    rd: Fmt = F32
    dataflow: str = "fused"
    codec_impl: str = "auto"
    # Packed-lane storage for the weight slot (DESIGN.md §9): rs2 travels as
    # uint16 lanes holding two p8 codes each (core/pack.py split-K layout).
    # Static, like dataflow — it changes operand shapes and the lowered kernel.
    rs2_packed: bool = False

    def __post_init__(self):
        if self.dataflow not in DATAFLOWS:
            raise ValueError(
                f"dataflow must be one of {DATAFLOWS}, got {self.dataflow!r}")
        if self.codec_impl not in CODEC_IMPLS:
            raise ValueError(
                f"codec_impl must be one of {CODEC_IMPLS}, got {self.codec_impl!r}")
        if self.rs2_packed and not (
                isinstance(self.rs2, PositFmt) and self.rs2.nbits == 8):
            raise ValueError(
                f"rs2_packed requires a p8 rs2 (two codes per 16-bit lane), "
                f"got {self.rs2}")

    @classmethod
    def uniform(cls, fmt: Fmt, dataflow: str = "fused",
                codec_impl: str = "auto") -> "OperandSlots":
        return cls(rs1=fmt, rs2=fmt, rs3=fmt, rd=fmt, dataflow=dataflow,
                   codec_impl=codec_impl)

    def with_dataflow(self, dataflow: str) -> "OperandSlots":
        return dataclasses.replace(self, dataflow=dataflow)

    def with_codec_impl(self, codec_impl: str) -> "OperandSlots":
        return dataclasses.replace(self, codec_impl=codec_impl)

    def with_packed(self, rs2_packed: bool = True) -> "OperandSlots":
        return dataclasses.replace(self, rs2_packed=rs2_packed)

    def encode_bits(self) -> int:
        """Pack into the paper's 4x(1+1+3)-bit register layout (for display),
        plus our dataflow extension in bits 20-21 (00 fused / 01 unfused /
        10 quire), the codec_impl extension in bits 22-23 (00 auto /
        01 lut / 10 bits) and the rs2 packed-lane bit in bit 24."""
        word = 0
        for i, f in enumerate((self.rs1, self.rs2, self.rs3, self.rd)):
            pfmt = 1 if isinstance(f, PositFmt) else 0
            pprec = 1 if (isinstance(f, PositFmt) and f.nbits == 16) else 0
            pes = f.es if isinstance(f, PositFmt) else 0
            word |= pfmt << i
            word |= pprec << (4 + i)
            word |= pes << (8 + 3 * i)
        word |= DATAFLOWS.index(self.dataflow) << 20
        word |= CODEC_IMPLS.index(self.codec_impl) << 22
        word |= int(self.rs2_packed) << 24
        return word


# Tensor roles a policy can assign a storage format to.
ROLES = (
    "weights",        # linear-layer parameters at rest / on the FSDP wire
    "activations",    # inter-layer activations (residual stream stays compute dtype)
    "gradients",      # gradient transport (cross-pod all-reduce payload)
    "kv_cache",       # attention KV cache at rest in HBM
    "optimizer",      # Adam moments at rest
    "collectives",    # generic collective payloads (compressed psum)
    "checkpoint",     # on-disk format
    "state",          # recurrent state (SSM/xLSTM h): quire-carried update
)


@dataclasses.dataclass(frozen=True)
class TransPolicy:
    """Which storage format each tensor role uses. ``None`` = native compute dtype.

    This is the whole-run pcsr: e.g. P16 weights + P8 KV cache + P16 gradient
    compression, while compute stays on the MXU in bf16/f32 (the paper's FPU).
    """

    weights: Optional[PositFmt] = None
    activations: Optional[PositFmt] = None
    gradients: Optional[PositFmt] = None
    kv_cache: Optional[PositFmt] = None
    optimizer: Optional[PositFmt] = None
    collectives: Optional[PositFmt] = None
    checkpoint: Optional[PositFmt] = None
    state: Optional[PositFmt] = None    # posit recurrent state, quire update
    compute_dtype: str = "f32"  # "f32" | "bf16" — the FPU-datapath dtype
    # Exact quire-domain psum for posit collective payloads: one encode
    # rounding per device + one readout rounding total, instead of re-rounding
    # at every reduction hop (distributed.collectives.quire_psum_posit).
    exact_collectives: bool = False
    # Codec implementation every layer-level decode/encode lowers to
    # (repro.core.lut): "auto" | "lut" | "bits".
    codec_impl: str = "auto"
    # Layer epilogue dataflow (repro.core.dot): "fused" keeps
    # bias/activation/residual/encode with the GEMM, "chained" materializes
    # each stage (the benchmark baseline).
    epilogue: str = "fused"
    # Packed-lane weight storage (core/pack.py): p8 weight codes travel two
    # per 16-bit lane through the memory system (DESIGN.md §9).  Only
    # meaningful for p8 weights; quantize_params / apply_linear consult it.
    pack_weights: bool = False
    # Decode-step attention dispatch (DESIGN.md §10): "kernel" sends every
    # decode step through kernels.posit_attention.ops (tile-wise in-VMEM
    # decode), "xla" keeps the full-cache-decode einsum path, "auto" picks
    # kernel wherever its contract covers the layer.
    attn_impl: str = "auto"
    # Linear-layer accumulation dataflow (repro.core.dot): "fused" decodes
    # into the f32/bf16 FPU matmul (the paper), "quire" accumulates every
    # posit product exactly with one terminal rounding (PERCIVAL; DESIGN.md
    # §7).  Applies to posit-coded plain linears; MoE expert-stack einsums
    # and the float-master training path stay on the fused FPU datapath.
    dataflow: str = "fused"

    def __post_init__(self):
        if self.dataflow not in POLICY_DATAFLOWS:
            raise ValueError(
                f"policy dataflow must be one of {POLICY_DATAFLOWS}, "
                f"got {self.dataflow!r}")
        if self.pack_weights and not (
                self.weights is not None and self.weights.nbits == 8):
            raise ValueError(
                "pack_weights requires p8 weights (two codes per lane), "
                f"got weights={self.weights}")
        if self.codec_impl not in CODEC_IMPLS:
            raise ValueError(
                f"codec_impl must be one of {CODEC_IMPLS}, got {self.codec_impl!r}")
        if self.epilogue not in EPILOGUES:
            raise ValueError(
                f"epilogue must be one of {EPILOGUES}, got {self.epilogue!r}")
        if self.attn_impl not in ATTN_IMPLS:
            raise ValueError(
                f"attn_impl must be one of {ATTN_IMPLS}, got {self.attn_impl!r}")

    def fmt_for(self, role: str) -> Optional[PositFmt]:
        if role not in ROLES:
            raise KeyError(f"unknown tensor role {role!r}; known: {ROLES}")
        return getattr(self, role)

    @classmethod
    def from_names(cls, compute_dtype: str = "f32",
                   exact_collectives: bool = False,
                   codec_impl: str = "auto", epilogue: str = "fused",
                   pack_weights: bool = False, attn_impl: str = "auto",
                   dataflow: str = "fused",
                   **roles: Optional[str]) -> "TransPolicy":
        kw = {"exact_collectives": exact_collectives,
              "codec_impl": codec_impl, "epilogue": epilogue,
              "pack_weights": pack_weights, "attn_impl": attn_impl,
              "dataflow": dataflow}
        for role, name in roles.items():
            if name is None or name == "none":
                kw[role] = None
                continue
            fmt = get_format(name)
            if not isinstance(fmt, PositFmt):
                raise ValueError(f"role {role} must be a posit format or none, got {name}")
            kw[role] = fmt
        return cls(compute_dtype=compute_dtype, **kw)

    @classmethod
    def from_spec(cls, spec: str) -> "TransPolicy":
        """Parse a command-line policy spec: ``none`` | ``p16-train`` |
        ``p8-serve`` | ``weights=p8_0,kv=p8_0,compute=bf16,...``."""
        if spec in ("none", ""):
            return cls()
        if spec == "p16-train":
            return cls.from_names(weights="p16_1", gradients="p16_1",
                                  optimizer="p16_1", checkpoint="p16_1")
        if spec == "p8-serve":
            return cls.from_names(weights="p8_0", kv_cache="p8_0",
                                  compute_dtype="bf16")
        kw = {}
        cd = "f32"
        for part in spec.split(","):
            k, v = part.split("=")
            if k == "compute":
                cd = v
            else:
                kw[{"kv": "kv_cache"}.get(k, k)] = v
        return cls.from_names(compute_dtype=cd, **kw)

    def to_json(self) -> dict:
        """JSON-ready dict: format roles by name, knobs verbatim.

        Round-trips through ``TransPolicy.from_json`` — the persistence layer
        calibration artifacts (DESIGN.md §11) embed their base policy with.
        """
        d = {role: (f.name if (f := self.fmt_for(role)) is not None else None)
             for role in ROLES}
        d.update(compute_dtype=self.compute_dtype,
                 exact_collectives=self.exact_collectives,
                 codec_impl=self.codec_impl, epilogue=self.epilogue,
                 pack_weights=self.pack_weights, attn_impl=self.attn_impl,
                 dataflow=self.dataflow)
        return d

    @classmethod
    def from_json(cls, d: dict) -> "TransPolicy":
        """Inverse of ``to_json``; unknown keys are rejected loudly."""
        known = set(ROLES) | {"compute_dtype", "exact_collectives",
                              "codec_impl", "epilogue", "pack_weights",
                              "attn_impl", "dataflow"}
        bad = set(d) - known
        if bad:
            raise ValueError(f"unknown TransPolicy fields {sorted(bad)}")
        kw = dict(d)
        for role in ROLES:
            if kw.get(role) is not None:
                fmt = get_format(kw[role])
                if not isinstance(fmt, PositFmt):
                    raise ValueError(
                        f"role {role} must be a posit format, got {kw[role]!r}")
                kw[role] = fmt
        return cls(**kw)

    def describe(self) -> str:
        parts = [f"compute={self.compute_dtype}"]
        for role in ROLES:
            f = self.fmt_for(role)
            parts.append(f"{role}={f.name if f else '-'}")
        if self.exact_collectives:
            parts.append("exact_collectives")
        if self.codec_impl != "auto":
            parts.append(f"codec={self.codec_impl}")
        if self.epilogue != "fused":
            parts.append(f"epilogue={self.epilogue}")
        if self.pack_weights:
            parts.append("packed_weights")
        if self.attn_impl != "auto":
            parts.append(f"attn={self.attn_impl}")
        if self.dataflow != "fused":
            parts.append(f"dataflow={self.dataflow}")
        return " ".join(parts)


# Canonical policies used across examples/benchmarks -----------------------------
FP32_POLICY = TransPolicy()  # pure IEEE path: every codec bypassed
BF16_COMPUTE = TransPolicy(compute_dtype="bf16")
P16_WEIGHTS = TransPolicy.from_names(weights="p16_1")
P8_WEIGHTS = TransPolicy.from_names(weights="p8_0", compute_dtype="bf16")
P8_SERVE = TransPolicy.from_names(weights="p8_0", kv_cache="p8_0", compute_dtype="bf16")
P16_TRAIN = TransPolicy.from_names(
    weights="p16_1", gradients="p16_1", optimizer="p16_1", checkpoint="p16_1"
)
# Exact-accumulation flavor: posit state carried through a quire, gradient
# psum in the quire domain (single rounding per device + readout).
P16_QUIRE = dataclasses.replace(
    TransPolicy.from_names(weights="p16_1", gradients="p16_1", state="p16_1"),
    exact_collectives=True,
)
