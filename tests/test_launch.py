"""Launch-layer unit tests: collective parsing, sharding rules, roofline math,
param counting, the dry-run cell driver — everything that doesn't need 512
devices."""
import dataclasses
import os

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import cells, get_arch, get_shape
from repro.core.pcsr import TransPolicy
from repro.launch import dryrun
from repro.launch.dryrun import parse_collectives
from repro.launch.roofline import analyse, model_flops, param_count
from repro.launch.sharding import param_spec


HLO_SAMPLE = """
HloModule test
ENTRY main {
  %p0 = f32[16,128]{1,0} parameter(0)
  %ar = f32[16,128]{1,0} all-reduce(%p0), replica_groups={}, to_apply=%add
  %ag = u8[64,128]{1,0} all-gather(%small), dimensions={0}
  %a2a = (u16[8,32]{1,0}, u16[8,32]{1,0}) all-to-all(%x, %y), dimensions={0}
  %rs-start = bf16[4,256]{1,0} reduce-scatter-start(%z), dimensions={0}
  ROOT %cp = f32[2,2]{1,0} collective-permute(%w), source_target_pairs={{0,1}}
}
"""


def test_parse_collectives():
    got = parse_collectives(HLO_SAMPLE)
    assert got["all-reduce"]["bytes"] == 16 * 128 * 4
    assert got["all-gather"]["bytes"] == 64 * 128 * 1
    assert got["all-to-all"]["bytes"] == 2 * 8 * 32 * 2
    assert got["reduce-scatter"]["bytes"] == 4 * 256 * 2
    assert got["collective-permute"]["bytes"] == 2 * 2 * 4
    assert got["all-reduce"]["count"] == 1
    assert got["all-gather"]["by_dtype"] == {"u8": 64 * 128}


def test_parse_collectives_skips_done():
    txt = "%x = f32[8]{0} all-reduce-start(%a)\n%y = f32[8]{0} all-reduce-done(%x)"
    got = parse_collectives(txt)
    assert got["all-reduce"]["count"] == 1  # start counted, done skipped


class FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 16, "model": 16}


def test_param_spec_rules():
    mesh = FakeMesh()
    # col-parallel: (in, out) -> (data, model)
    assert param_spec("blocks/attn/wq/w", (60, 7168, 7168), mesh) == \
        P(None, "data", "model")
    # row-parallel
    assert param_spec("blocks/mlp/down/w", (60, 20480, 7168), mesh) == \
        P(None, "model", "data")
    # experts: E over model (EP)
    assert param_spec("blocks/moe/w_gate", (16, 64, 2048, 1024), mesh) == \
        P(None, "model", "data", None)
    # embedding: vocab over model when divisible
    assert param_spec("embed/table", (64000, 7168), mesh) == P("model", "data")
    # granite's 49155 vocab is not divisible -> unsharded vocab dim
    assert param_spec("embed/table", (49155, 1536), mesh) == P(None, "data")
    # optimizer moments mirror the parameter
    assert param_spec("mu/blocks/attn/wq/w/m", (60, 7168, 7168), mesh) == \
        P(None, "data", "model")
    # norms replicate
    assert param_spec("blocks/ln1/g", (60, 7168), mesh) == P(None, None)
    # posit-coded weights shard like their float counterparts
    assert param_spec("blocks/attn/wq/w_codes", (60, 7168, 7168), mesh) == \
        P(None, "data", "model")


def test_cells_assignment_matrix():
    """40 cells total; 7 long_500k skips for full-attention archs (DESIGN §6)."""
    all_cells = list(cells(include_skipped=True))
    assert len(all_cells) == 40
    skips = [(c.name, s.name) for c, s, sk in all_cells if sk]
    assert len(skips) == 7
    assert all(s == "long_500k" for _, s in skips)
    runnable = list(cells())
    assert len(runnable) == 33
    long_archs = {c.name for c, s, _ in runnable if s.name == "long_500k"}
    assert long_archs == {"zamba2-7b", "gemma3-4b", "xlstm-125m"}


def test_param_count_sane():
    """Analytic param counts should be within ~15% of the nominal sizes."""
    nominal = {"yi-34b": 34e9, "phi3-mini-3.8b": 3.8e9,
               "qwen2.5-14b": 14e9, "olmoe-1b-7b": 7e9}
    for arch, n in nominal.items():
        total, active = param_count(get_arch(arch))
        assert 0.8 * n < total < 1.25 * n, (arch, total)
        assert active <= total
    # olmoe: ~1B active of ~7B total
    total, active = param_count(get_arch("olmoe-1b-7b"))
    assert active < 0.35 * total


def test_model_flops_scaling():
    cfg = get_arch("phi3-mini-3.8b")
    tr = model_flops(cfg, get_shape("train_4k"))
    pf = model_flops(cfg, get_shape("prefill_32k"))
    dc = model_flops(cfg, get_shape("decode_32k"))
    # train = 6ND on 1M tokens; prefill = 2ND on 1M tokens -> 3x
    assert abs(tr / pf - 3.0) < 1e-6
    # decode: 128 tokens vs 1M -> tiny
    assert dc < pf / 1000


@pytest.mark.parametrize("spec,want", [
    ("none", {}),
    ("p8-serve", {"weights": "p8_0", "kv_cache": "p8_0", "compute": "bf16"}),
    ("weights=p16_1,kv=p8_0,compute=bf16",
     {"weights": "p16_1", "kv_cache": "p8_0", "compute": "bf16"}),
])
def test_policy_spec_parse_leaves_xla_flags(monkeypatch, spec, want):
    """Parsing a policy spec, and importing the serving and training entry
    points, never rewrites the operator's ``XLA_FLAGS``."""
    import importlib

    monkeypatch.setenv("XLA_FLAGS", "--operator-flag")
    for mod in ("repro.launch.serve", "repro.launch.server",
                "repro.launch.train", "repro.launch.dryrun"):
        importlib.import_module(mod)
    pol = TransPolicy.from_spec(spec)
    assert os.environ["XLA_FLAGS"] == "--operator-flag"
    got = {r: getattr(pol, r).name for r in ("weights", "kv_cache")
           if getattr(pol, r) is not None}
    if pol.compute_dtype != "f32":
        got["compute"] = pol.compute_dtype
    assert got == want


def test_dryrun_run_cell(monkeypatch):
    """The dry-run driver end to end on a reduced cell and a 1-chip mesh:
    lower, compile, extract memory/cost/collectives without error (covers
    the cost_analysis list/dict normalization in situ)."""
    cfg = get_arch("phi3-mini-3.8b").reduced()
    shape = dataclasses.replace(
        get_shape("decode_32k"), seq_len=64, global_batch=4)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
    monkeypatch.setattr(dryrun, "get_arch", lambda name: cfg)
    monkeypatch.setattr(dryrun, "get_shape", lambda name: shape)
    monkeypatch.setattr(dryrun, "make_production_mesh",
                        lambda *, multi_pod: mesh)
    res = dryrun.run_cell("phi3-mini-3.8b", "decode_32k", multi_pod=False,
                          policy=TransPolicy.from_spec("p8-serve"))
    assert "error" not in res
    assert res["n_chips"] == 1
    assert res["flops_per_device"] >= 0
    assert res["memory"]["argument_bytes"] > 0


def test_roofline_analyse():
    rec = {
        "arch": "phi3-mini-3.8b", "shape": "train_4k", "kind": "train",
        "multi_pod": False, "n_chips": 256,
        "flops_per_device": 1.1e14, "bytes_per_device": 2.0e11,
        "memory": {}, "collectives": {"all-reduce": {"bytes": 5e9, "count": 3}},
    }
    out = analyse(rec)
    assert out["dominant"] in ("compute", "memory", "collective")
    assert out["t_compute_s"] == pytest.approx(1.1e14 / 197e12)
    assert out["t_memory_s"] == pytest.approx(2.0e11 / 819e9)
    assert out["t_collective_s"] == pytest.approx(5e9 / 50e9)
    assert 0 < out["useful_ratio"] < 10
    assert out["roofline_fraction"] > 0
