"""The serving path that runs on the chip, checked on the CPU.

* the leaf-wise weight loader (``layers.init_quantized_params``) gives the
  same bits as a whole-model ``init`` followed by ``quantize_params``, so a
  model too large for f32-then-quantize loads the weights it would have had,
* ``build_server`` honours ``quantize_weights``: the engine it builds holds
  posit codes and serves with them,
* ``chip_smoke.py`` refuses to run anywhere but on a TPU, and its phases
  pass end to end at the reduced size when a test lets it run on the CPU.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.pcsr import TransPolicy
from repro.launch.config import ServeConfig
from repro.launch.engine import Request
from repro.models.layers import init_quantized_params, quantize_params
from repro.models.registry import build_model

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("arch,spec", [
    ("phi3-mini-3.8b", "p8-serve"),
    ("phi3-mini-3.8b", "weights=p16_1,kv=p8_0"),
    ("olmoe-1b-7b", "p8-serve"),          # MoE expert stacks ("<name>_codes")
])
def test_leafwise_init_quantize_bit_identical(arch, spec):
    model = build_model(get_arch(arch).reduced())
    policy = TransPolicy.from_spec(spec)
    key = jax.random.key(3)
    want = quantize_params(model.init(key), policy)
    got = init_quantized_params(model.init, key, policy)
    w_leaves, w_def = jax.tree.flatten(want)
    g_leaves, g_def = jax.tree.flatten(got)
    assert w_def == g_def
    for w, g in zip(w_leaves, g_leaves):
        assert w.dtype == g.dtype and w.shape == g.shape
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


def test_build_server_quantize_weights_serves_codes():
    from repro.launch.server import build_server

    scfg = ServeConfig(arch="phi3-mini-3.8b", reduced=True, continuous=True,
                       paged=True, policy="p8-serve", quantize_weights=True,
                       max_slots=2, prompt_len=8, gen=4, port=0).validate()
    eng = build_server(scfg).driver.engine
    attn = eng.params["blocks"]["attn"]
    assert "w" not in attn["wq"] and attn["wq"]["w_codes"].dtype == np.uint8
    assert eng.params["lm_head"]["w_codes"].dtype == np.uint8
    (comp,) = eng.run([Request(rid=0, prompt=np.arange(1, 9, dtype=np.int32),
                               max_new_tokens=4)])
    assert comp.finish_reason in ("max_new", "eos") and comp.tokens


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300,
                         cwd=ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_phases_pass_reduced_on_cpu(capsys):
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    assert chip_smoke.main(["--reduced"], platform="cpu") == 0
    lines = capsys.readouterr().out.strip().splitlines()
    phases = [json.loads(ln)["phase"] for ln in lines
              if ln.startswith('{"phase"')]
    assert phases == ["device", "kernel", "server", "slot_grid"]
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
