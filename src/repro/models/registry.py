"""Model registry: one uniform interface over all families.

  model = build_model(cfg)
  params = model.init(key)
  loss, metrics = model.loss(params, batch, policy)
  logits, cache = model.prefill(params, ..., policy) / model.decode_step(...)

The VLM family reuses the decoder-only path with a stubbed patch-embedding
prefix (assignment: modality frontends are stubs).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax

from repro.configs.base import ModelCfg
from repro.models import encdec, transformer

# Init is one XLA program per config.  XLA folds the constants of a traced
# graph the same way whether it keeps every leaf or one, so the leaf-wise
# serving loader (layers.init_quantized_params) is bit-identical to this
# init; eager op-by-op init would differ from it in the last bit of scaled
# weights.
_init_lm = jax.jit(transformer.init_lm, static_argnums=1)
_init_encdec = jax.jit(encdec.init_encdec, static_argnums=1)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelCfg
    init: Callable
    loss: Callable            # (params, batch, policy) -> (loss, metrics)
    forward: Callable         # (params, batch, policy) -> hidden
    init_cache: Callable      # serving
    prefill: Callable
    decode_step: Callable
    # paged serving entry points (DESIGN.md §14) — None for families whose
    # cache layout the block pool cannot express (window buffers, recurrent
    # state, patch prefixes)
    init_paged_cache: Callable = None
    decode_step_paged: Callable = None


def build_model(cfg: ModelCfg) -> Model:
    if cfg.family == "whisper":
        return Model(
            cfg=cfg,
            init=lambda key: _init_encdec(key, cfg),
            loss=lambda p, b, pol: encdec.encdec_loss(p, b, cfg, pol),
            forward=lambda p, b, pol: encdec.decode_train(
                p, b["tokens"], encdec.encode(p, b["frames"], cfg, pol), cfg, pol),
            init_cache=lambda p, b, pol, S_max: encdec.init_dec_cache(
                p, b["frames"], cfg, pol, S_max),
            prefill=None,
            decode_step=lambda p, tok, cache, pol: encdec.decode_step(
                p, tok, cache, cfg, pol),
        )

    def loss(p, b, pol):
        return transformer.lm_loss(p, b, cfg, pol)

    def fwd(p, b, pol):
        h, _ = transformer.forward(p, b["tokens"], cfg, pol,
                                   patch_embeds=b.get("patch_embeds"))
        return h

    return Model(
        cfg=cfg,
        init=lambda key: _init_lm(key, cfg),
        loss=loss,
        forward=fwd,
        init_cache=lambda B, S_max, pol: transformer.init_cache(cfg, B, S_max, pol),
        prefill=lambda p, tokens, pol, **kw: transformer.prefill(
            p, tokens, cfg, pol, **kw),
        decode_step=lambda p, tok, cache, pol: transformer.decode_step(
            p, tok, cache, cfg, pol),
        init_paged_cache=(
            lambda B, n_blocks, bt, width, pol: transformer.init_paged_cache(
                cfg, B, n_blocks, bt, width, pol))
        if cfg.family in ("dense", "moe") else None,
        decode_step_paged=(
            lambda p, tok, cache, pol: transformer.decode_step_paged(
                p, tok, cache, cfg, pol))
        if cfg.family in ("dense", "moe") else None,
    )
