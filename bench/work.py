"""Operations and bytes that serving work needs, counted from shapes.

The counts are what the mathematics of a step requires, whatever the program
does to compute it: every linear weight read once at its stored code width,
the K/V codes of each row's live length, one new K/V row per row written,
and 2 FLOPs per multiply-add.  ``m`` is a configuration file's published
sizes (``hidden_size``, ``num_attention_heads``, ...).
"""
from __future__ import annotations


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def _attn_params(m: dict) -> int:
    d, hd = m["hidden_size"], head_dim(m)
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d


def _expert_params(m: dict) -> int:
    """One expert's (or the dense MLP's) gate, up and down weights."""
    return 3 * m["hidden_size"] * m["intermediate_size"]


def is_moe(m: dict) -> bool:
    return bool(m.get("num_experts"))


def layer_fixed_params(m: dict) -> int:
    """Weights of one layer that every token uses: attention, and the dense
    MLP or the MoE router."""
    mlp = (m["hidden_size"] * m["num_experts"] if is_moe(m)
           else _expert_params(m))
    return _attn_params(m) + mlp


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def active_params(m: dict, *, lm_head: bool = True) -> int:
    """Weights one token multiplies through (embedding lookup excluded)."""
    per_layer = layer_fixed_params(m)
    if is_moe(m):
        per_layer += m["num_experts_per_tok"] * _expert_params(m)
    return (m["num_hidden_layers"] * per_layer
            + (head_params(m) if lm_head else 0))


def experts_reached(m: dict, rows: int) -> float:
    """Expected experts one layer's routing reaches for ``rows`` tokens:
    E * (1 - (1 - k/E)^rows)."""
    E, k = m["num_experts"], m["num_experts_per_tok"]
    return E * (1.0 - (1.0 - k / E) ** rows)


def weight_bytes(m: dict, rows: int, code_bytes: float) -> float:
    """Linear weights a step over ``rows`` tokens must read, once each."""
    per_layer = layer_fixed_params(m)
    if is_moe(m):
        per_layer += experts_reached(m, rows) * _expert_params(m)
    return code_bytes * (m["num_hidden_layers"] * per_layer + head_params(m))


def _kv_row_bytes(m: dict, kv_bytes: float) -> float:
    """K and V codes of one token in one layer."""
    return 2.0 * m["num_key_value_heads"] * head_dim(m) * kv_bytes


def decode_step(m: dict, lens, *, code_bytes: float = 1.0,
                kv_bytes: float = 1.0) -> dict:
    """One decode step over the live rows; ``lens`` holds each live row's
    context length including the token this step writes."""
    L, hq, hd = m["num_hidden_layers"], m["num_attention_heads"], head_dim(m)
    lens = [int(x) for x in lens]
    B = len(lens)
    ctx = sum(lens)
    flops = 2.0 * active_params(m) * B + 4.0 * hq * hd * ctx * L
    byts = (weight_bytes(m, B, code_bytes)
            + L * _kv_row_bytes(m, kv_bytes) * (ctx + B))
    return {"flops": flops, "bytes": byts}


def prefill(m: dict, T: int, *, code_bytes: float = 1.0,
            kv_bytes: float = 1.0) -> dict:
    """One B=1 prefill of ``T`` prompt tokens: every position through every
    layer, causal attention (the lower triangle, T(T+1)/2 pairs), logits at
    the last position only."""
    L, hq, hd = m["num_hidden_layers"], m["num_attention_heads"], head_dim(m)
    flops = (2.0 * active_params(m, lm_head=False) * T
             + 2.0 * head_params(m)
             + 4.0 * hq * hd * L * T * (T + 1) / 2.0)
    byts = weight_bytes(m, T, code_bytes) + L * _kv_row_bytes(m, kv_bytes) * T
    return {"flops": flops, "bytes": byts}


def min_time(work: dict, peaks: dict) -> float:
    """The least time the chip could take: the larger of compute and
    memory time."""
    return max(work["flops"] / peaks["flops"], work["bytes"] / peaks["hbm_bw"])
