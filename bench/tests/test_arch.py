"""Tests of the architecture modules (``reference/__init__.py``), on the CPU.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

The dense and MoE modules serve the weights and pages, and compute the
reference logits, that the benchmark did before it had modules
(fingerprints taken then, frozen here); a
module that reuses a leaf id, or gives a field the registered architecture
does not have, is refused; and an architecture that no file of the
benchmark knows, ``fixture/dense_bias.py`` (dense GQA with q/k/v biases and
a head width unequal to hidden_size / num_attention_heads), runs a whole
cell correctly as new files only, while its int4 control fails.
"""
from __future__ import annotations

import hashlib
import io
import json
import pathlib
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
FIXTURE = BENCH / "tests" / "fixture"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import check  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402

SEED = 2**33 + 5


def config(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def served(cfg: dict, *, test_size: bool):
    """[(path, leaf)] of the served param tree: made at the test size,
    shapes only at the published size."""
    import jax
    from repro.models.layers import quantize_params
    m = {**cfg, **cfg["test_size"]} if test_size else cfg
    cell = harness.Cell("x", cfg, m, traffic.load("chat-closed",
                                                  test_size=test_size), SEED)
    policy, _ = harness.serve_config(cell).build_policy()
    make = lambda: weights.served_params(m, SEED, policy,  # noqa: E731
                                         quantize_params)
    tree = make() if test_size else jax.eval_shape(make)
    return [("/".join(k.key for k in path), x)
            for path, x in jax.tree_util.tree_leaves_with_path(tree)]


# Taken from the benchmark as it was before the architecture modules: the
# sha256 of every served leaf's bytes at the test size (seed 2**33 + 5), and
# every leaf's shape and type at the published size.
BEFORE = {
    "phi3-mini-3.8b-p8": (
        "e826561381fb09ff539f12925799e37e834064e60957b345fd5bee672bbfe247", {
            "blocks/attn/wk/w_codes": ((32, 3072, 3072), "uint8"),
            "blocks/attn/wo/w_codes": ((32, 3072, 3072), "uint8"),
            "blocks/attn/wq/w_codes": ((32, 3072, 3072), "uint8"),
            "blocks/attn/wv/w_codes": ((32, 3072, 3072), "uint8"),
            "blocks/ln1/g": ((32, 3072), "float32"),
            "blocks/ln2/g": ((32, 3072), "float32"),
            "blocks/mlp/down/w_codes": ((32, 8192, 3072), "uint8"),
            "blocks/mlp/gate/w_codes": ((32, 3072, 8192), "uint8"),
            "blocks/mlp/up/w_codes": ((32, 3072, 8192), "uint8"),
            "embed/table": ((32064, 3072), "float32"),
            "final_norm/g": ((3072,), "float32"),
            "lm_head/w_codes": ((3072, 32064), "uint8")}),
    "olmoe-1b-7b-p8": (
        "82d1db9d32a6e8c85f2cd619d9bea2a433549499a73168323bbc2216c785aad6", {
            "blocks/attn/wk/w_codes": ((16, 2048, 2048), "uint8"),
            "blocks/attn/wo/w_codes": ((16, 2048, 2048), "uint8"),
            "blocks/attn/wq/w_codes": ((16, 2048, 2048), "uint8"),
            "blocks/attn/wv/w_codes": ((16, 2048, 2048), "uint8"),
            "blocks/ln1/g": ((16, 2048), "float32"),
            "blocks/ln2/g": ((16, 2048), "float32"),
            "blocks/moe/router/w_codes": ((16, 2048, 64), "uint8"),
            "blocks/moe/w_down_codes": ((16, 64, 1024, 2048), "uint8"),
            "blocks/moe/w_gate_codes": ((16, 64, 2048, 1024), "uint8"),
            "blocks/moe/w_up_codes": ((16, 64, 2048, 1024), "uint8"),
            "embed/table": ((50304, 2048), "float32"),
            "final_norm/g": ((2048,), "float32"),
            "lm_head/w_codes": ((2048, 50304), "uint8")}),
}


@pytest.mark.parametrize("name", list(BEFORE))
def test_served_weights_are_unchanged(name):
    cfg = config(BENCH / "configs" / f"{name}.json")
    sha, shapes = BEFORE[name]
    h = hashlib.sha256()
    for _, x in served(cfg, test_size=True):
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == sha
    got = {p: (tuple(x.shape), str(x.dtype))
           for p, x in served(cfg, test_size=False)}
    assert got == shapes


LOGITS = config(FIXTURE / "logits_before.json")


@pytest.mark.parametrize("quant", [None, "int4"], ids=["float32", "int4"])
@pytest.mark.parametrize("name", list(BEFORE))
def test_reference_logits_are_unchanged(name, quant):
    """The reference's logits (and the int4 control's) at the test size, on
    two prompts across two query blocks, against the ones the benchmark
    computed before the architecture modules (``fixture/logits_before.json``:
    each row's norm and best token, and the logits at eight columns)."""
    from reference import common
    cfg = config(BENCH / "configs" / f"{name}.json")
    m = {**cfg, **cfg["test_size"]}
    rng = np.random.default_rng(7)
    seqs = [rng.integers(0, m["vocab_size"], n).tolist()
            for n in LOGITS["lengths"]]
    x = np.asarray(common.logits_at(m, LOGITS["seed"], seqs,
                                    LOGITS["positions"], quant=quant),
                   np.float64)
    want = LOGITS["logits"][f"{name}/{quant or 'float32'}"]
    assert np.argmax(x, axis=1).tolist() == want["argmax"]
    np.testing.assert_allclose(np.linalg.norm(x, axis=1), want["norm"],
                               rtol=1e-6)
    np.testing.assert_allclose(x[:, LOGITS["columns"]], want["at_columns"],
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("name", list(BEFORE))
def test_pages_hold_16_tokens_at_the_published_size(name):
    cfg = config(BENCH / "configs" / f"{name}.json")
    mix = traffic.load("chat-closed")
    scfg = harness.serve_config(harness.Cell("x", cfg, cfg, mix, SEED))
    assert reference.load(cfg).page_row_bytes(cfg) * 16 == scfg.page_bytes
    assert scfg.n_blocks == mix["kv_pool_tokens"] // 16


# ------------------------------------------------------------- refusals ---

@pytest.fixture
def modules_in(monkeypatch):
    """Adds a directory to the ``reference`` package's path; the modules
    imported from it are forgotten afterwards."""
    added = []

    def add(where: pathlib.Path):
        monkeypatch.setattr(reference, "__path__",
                            [*reference.__path__, str(where)])
        added.extend(f"reference.{p.stem}" for p in where.glob("*.py"))
    yield add
    for name in added:
        sys.modules.pop(name, None)


@pytest.mark.parametrize(
    "ids", [{"wq_again": 1}, {"wq": 99}, {"twin_a": 90, "twin_b": 90}],
    ids=["id_in_use", "name_under_another_id", "one_id_twice_in_a_call"])
def test_a_module_that_reuses_a_leaf_id_is_refused(ids, tmp_path, modules_in):
    (tmp_path / "clash.py").write_text(
        f"import weights\nweights.declare({ids!r})\n")
    modules_in(tmp_path)
    reference.load({"reference": "dense"})
    with pytest.raises(ValueError, match="already"):
        reference.load({"reference": "clash"})
    assert not {"wq_again", "twin_a", "twin_b"} & set(weights._IDS)
    assert weights._IDS["wq"] == 1


# A module that gives no ``qkv_bias``, so the field keeps ModelCfg's default.
FORGETFUL = """from reference.dense import *  # noqa: F401,F403
from reference import dense


def model_fields(m):
    f = dense.model_fields(m)
    del f["qkv_bias"]
    return f
"""


@pytest.mark.parametrize("file,module", [
    (BENCH / "configs" / "phi3-mini-3.8b-p8.json", "dense_bias"),
    (FIXTURE / "dense-bias.json", "dense"),
    (FIXTURE / "dense-bias.json", "forgetful"),
], ids=["phi3_with_biases", "qwen_without_biases",
        "qwen_module_that_leaves_out_biases"])
def test_a_field_the_registered_architecture_lacks_is_refused(
        file, module, tmp_path, modules_in):
    (tmp_path / "forgetful.py").write_text(FORGETFUL)
    modules_in(FIXTURE)
    modules_in(tmp_path)
    cfg = config(file)
    harness.check_registered(cfg, harness.model_cfg(cfg, cfg))
    cfg["reference"] = module
    with pytest.raises(ValueError, match="qkv_bias"):
        harness.check_registered(cfg, harness.model_cfg(cfg, cfg))


# ------------------------------------------------ a new architecture ---

CELL = {"name": "dense-bias-chat-closed", "config": "dense-bias",
        "traffic": "chat-closed", "chips": 1,
        "why": "test fixture: the ShareGPT-shaped chat on dense GQA with "
               "q/k/v biases and a head width of its own"}


@pytest.fixture
def new_cell(monkeypatch, modules_in):
    """The fixture's cell, found where a cell of ``BENCHMARK.json`` and its
    limits would be."""
    modules_in(FIXTURE)
    cfg = config(FIXTURE / "dense-bias.json")
    bench = config(ROOT / "BENCHMARK.json")
    bench["workloads"].append(CELL)
    monkeypatch.setattr(run, "load_cell", lambda w: (bench, CELL, cfg))
    monkeypatch.setattr(check, "HERE", FIXTURE)
    return cfg


def test_a_new_architecture_runs_a_cell_correctly(new_cell):
    m = {**new_cell, **new_cell["test_size"]}
    assert m["head_dim"] != m["hidden_size"] // m["num_attention_heads"]
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", CELL["name"], "--seed", str(2**34 + 9),
                       "--seconds", "3", "--trace", "0", "--test-size"],
                      platform="cpu")
    assert rc == 0
    res = json.loads(buf.getvalue().splitlines()[-1])
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}


def test_the_new_architectures_control_fails(new_cell):
    import control
    m = {**new_cell, **new_cell["test_size"]}
    mix = traffic.load("chat-closed", test_size=True)
    limits = check.limits(CELL["name"], test_size=True)
    for seed in (21, 2**35 + 22, 23):
        r = control.one_seed(CELL, new_cell, m, mix, seed, 2.0)
        assert check.compare(r["program"], limits)[0], r
        assert not check.compare(r["control"], limits)[0], r
