"""Architecture modules: everything the benchmark knows of an architecture.

A configuration file's ``reference`` key names one module of this package,
``reference/<name>.py``, and only that module knows the architecture's
leaves, sizes and mathematics.  The harness, the weights, the work counts
and the correctness check reach it through :func:`load`.  A module gives:

``model_fields(m)``
    The program's ``ModelCfg`` fields beyond the ones every configuration
    has (``family``, ``n_layers``, ``d_model``, ``vocab``, ``norm_eps``,
    ``tie_embeddings``, ``capacity_factor``).  At the published size the
    whole ``ModelCfg`` is compared with the registered architecture
    (``harness.check_registered``).
``page_row_bytes(m)``
    The paged cache's bytes per token per layer at one byte a code; the
    page's tokens are the configuration's ``page_bytes`` over it.
``stacks(m)``
    ``{name: range}``: the program's stacks of layers, each with the model's
    layer indices it holds, in the order the forward runs them.
``layer_floats(m, words, stack, layer)``, ``top_floats(m, words)``
    One layer's float32 weights keyed as the program's param tree, and the
    leaves outside the stacks, from ``weights.normal`` and ``weights.gain``
    under leaf ids the module declares with ``weights.declare``.
``active_params(m)``, ``weights_read(m, rows)``, ``attn_work(m, phase)``
    The pieces of the work counts (``work.py``): the weights one token
    multiplies through and those a step over ``rows`` tokens reads, both
    without the output head; and, for ``phase`` ``"decode"`` or
    ``"prefill"``, ``{"pair_flops", "cache_row"}``: the FLOPs of one (query,
    context token) pair and the cache codes one token holds, all layers
    together.
``attn(x, lo, hi, stack, layer, mj, quant)``, ``mlp(h, lo, hi, stack,
layer, mj, quant)``
    The two halves of one layer of the plain float32 reference
    (``common.logits_at`` runs them stack by stack): ``attn`` returns the
    residual stream after attention and its normed input to the MLP.
    ``stack`` is static, so the kind of a layer is known when it is traced.
"""
from __future__ import annotations

import importlib


def load(m: dict):
    """The architecture module a configuration names."""
    return importlib.import_module(f"{__name__}.{m['reference']}")
