"""Operations and bytes that serving work needs, counted from shapes.

The counts are what the mathematics of a step requires, whatever the program
does to compute it: every linear weight read once at its stored code width,
the cache codes of each row's live length, one new cache row per row
written, and 2 FLOPs per multiply-add.  ``m`` is a configuration file's
published sizes (``hidden_size``, ``num_attention_heads``, ...); what a
layer holds is its architecture module's (``reference/<name>.py``), and
this file keeps what every module shares.
"""
from __future__ import annotations

import reference


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def active_params(m: dict, *, lm_head: bool = True) -> int:
    """Weights one token multiplies through (embedding lookup excluded)."""
    return (reference.load(m).active_params(m)
            + (head_params(m) if lm_head else 0))


def weight_bytes(m: dict, rows: int, code_bytes: float) -> float:
    """Linear weights a step over ``rows`` tokens must read, once each."""
    return code_bytes * (reference.load(m).weights_read(m, rows)
                         + head_params(m))


def experts_reached(m: dict, rows: int) -> float:
    """Expected experts one layer's routing reaches for ``rows`` tokens:
    E * (1 - (1 - k/E)^rows)."""
    E, k = m["num_experts"], m["num_experts_per_tok"]
    return E * (1.0 - (1.0 - k / E) ** rows)


def decode_step(m: dict, lens, *, code_bytes: float = 1.0,
                kv_bytes: float = 1.0) -> dict:
    """One decode step over the live rows; ``lens`` holds each live row's
    context length including the token this step writes.  Every weight is
    read once, the live rows' cache read and one cache row written per
    row."""
    a = reference.load(m).attn_work(m, "decode")
    lens = [int(x) for x in lens]
    B = len(lens)
    ctx = sum(lens)
    flops = 2.0 * active_params(m) * B + a["pair_flops"] * ctx
    byts = (weight_bytes(m, B, code_bytes)
            + a["cache_row"] * kv_bytes * (ctx + B))
    return {"flops": flops, "bytes": byts}


def prefill(m: dict, T: int, *, code_bytes: float = 1.0,
            kv_bytes: float = 1.0) -> dict:
    """One B=1 prefill of ``T`` prompt tokens: every position through every
    layer, causal attention (the lower triangle, T(T+1)/2 pairs), logits at
    the last position only, ``T`` cache rows written."""
    a = reference.load(m).attn_work(m, "prefill")
    flops = (2.0 * active_params(m, lm_head=False) * T
             + 2.0 * head_params(m)
             + a["pair_flops"] * T * (T + 1) / 2.0)
    byts = weight_bytes(m, T, code_bytes) + a["cache_row"] * kv_bytes * T
    return {"flops": flops, "bytes": byts}


def min_time(work: dict, peaks: dict) -> float:
    """The least time the chip could take: the larger of compute and
    memory time."""
    return max(work["flops"] / peaks["flops"], work["bytes"] / peaks["hbm_bw"])
