"""Substrate tests: optimizer (+posit moments), data pipeline determinism,
checkpoint atomicity/async/elastic restore, fault-tolerance runtime."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint.ckpt import (CheckpointManager, gc_tmp, latest_checkpoint,
                                   load_checkpoint, save_checkpoint)
from repro.core.types import P16_1
from repro.data.pipeline import SyntheticLMPipeline
from repro.ft.runtime import (FaultTolerantLoop, PreemptionSignal,
                              StragglerMonitor, with_retries)
from repro.optim import AdamWConfig, adamw_init, adamw_update, clip_by_global_norm
from repro.optim.schedule import cosine_warmup


# ------------------------------------------------------------- optimizer ------
def _quad_problem():
    params = {"w": jnp.asarray([3.0, -2.0, 1.0]), "b": jnp.asarray([0.5])}
    def loss(p):
        return jnp.sum(p["w"] ** 2) + jnp.sum(p["b"] ** 2)
    return params, loss


@pytest.mark.parametrize("fmt", [None, P16_1])
def test_adamw_converges(fmt):
    params, loss = _quad_problem()
    cfg = AdamWConfig(lr=0.05, weight_decay=0.0, moment_fmt=fmt)
    state = adamw_init(params, cfg)
    for _ in range(300):
        grads = jax.grad(loss)(params)
        params, state = adamw_update(grads, state, params, cfg)
    assert float(loss(params)) < 1e-2, float(loss(params))


def test_adamw_posit_moments_storage_dtype():
    params, loss = _quad_problem()
    cfg = AdamWConfig(moment_fmt=P16_1)
    state = adamw_init(params, cfg)
    assert state["mu"]["w"]["m"].dtype == jnp.uint16
    grads = jax.grad(loss)(params)
    params, state = adamw_update(grads, state, params, cfg)
    assert state["mu"]["w"]["m"].dtype == jnp.uint16
    assert state["mu"]["w"]["em"].dtype == jnp.float32  # error feedback


def test_error_feedback_tracks_true_moments():
    """Posit-compressed moments + EF must stay close to the f32 trajectory."""
    params, loss = _quad_problem()
    c_f32 = AdamWConfig(lr=0.01, weight_decay=0.0)
    c_p = AdamWConfig(lr=0.01, weight_decay=0.0, moment_fmt=P16_1,
                      error_feedback=True)
    p1, s1 = dict(params), adamw_init(params, c_f32)
    p2, s2 = dict(params), adamw_init(params, c_p)
    for _ in range(100):
        g1 = jax.grad(loss)(p1)
        p1, s1 = adamw_update(g1, s1, p1, c_f32)
        g2 = jax.grad(loss)(p2)
        p2, s2 = adamw_update(g2, s2, p2, c_p)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                               rtol=0.02, atol=5e-3)


def test_clip_and_schedule():
    g = {"a": jnp.asarray([3.0, 4.0])}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(norm) - 5.0) < 1e-5
    np.testing.assert_allclose(
        np.asarray(clipped["a"]), np.asarray([0.6, 0.8]), rtol=1e-5)
    assert float(cosine_warmup(jnp.asarray(0), warmup=10, total=100)) == 0.0
    assert abs(float(cosine_warmup(jnp.asarray(10), warmup=10, total=100)) - 1.0) < 1e-5
    assert float(cosine_warmup(jnp.asarray(100), warmup=10, total=100)) < 0.11


# --------------------------------------------------------------- pipeline -----
def test_pipeline_deterministic_and_sharded():
    kw = dict(vocab=101, seq_len=16, global_batch=8, seed=7)
    p0 = SyntheticLMPipeline(n_shards=2, shard=0, **kw)
    p1 = SyntheticLMPipeline(n_shards=2, shard=1, **kw)
    b0a, b0b = p0.batch_at(3), p0.batch_at(3)
    assert (np.asarray(b0a["tokens"]) == np.asarray(b0b["tokens"])).all()
    b1 = p1.batch_at(3)
    assert not (np.asarray(b0a["tokens"]) == np.asarray(b1["tokens"])).all()
    assert b0a["tokens"].shape == (4, 16)
    # labels are next-token shifted
    assert (np.asarray(b0a["labels"])[:, :-1] == np.asarray(b0a["tokens"])[:, 1:]).all()
    # different steps differ
    b2 = p0.batch_at(4)
    assert not (np.asarray(b0a["tokens"]) == np.asarray(b2["tokens"])).all()


def test_pipeline_has_learnable_structure():
    p = SyntheticLMPipeline(vocab=64, seq_len=256, global_batch=4, seed=0)
    b = p.batch_at(0)
    t = np.asarray(b["tokens"])
    follows = (t[:, 1:] == (t[:, :-1] + p._shift) % 64).mean()
    assert follows > 0.3, follows  # induced bigram structure present


# -------------------------------------------------------------- checkpoint ----
def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"layer": {"w": jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32)),
                      "step": jnp.asarray(5, jnp.int32)},
            "moments": [jnp.ones((3,)), jnp.zeros((2, 2))]}


def test_checkpoint_roundtrip(tmp_path):
    tree = _tree()
    save_checkpoint(str(tmp_path), 3, tree)
    restored, manifest = load_checkpoint(str(tmp_path), tree)
    assert manifest["step"] == 3
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_checkpoint_posit_compressed(tmp_path):
    tree = _tree(1)
    save_checkpoint(str(tmp_path), 1, tree, fmt=P16_1)
    restored, _ = load_checkpoint(str(tmp_path), tree)
    # float leaves round-trip through p16 (small values -> ~1e-3 rel error)
    np.testing.assert_allclose(np.asarray(tree["layer"]["w"]),
                               np.asarray(restored["layer"]["w"]),
                               rtol=1e-3, atol=1e-4)
    # int leaves stay exact
    assert int(restored["layer"]["step"]) == 5
    # and on-disk float payload is half size (p16 codes are uint16)
    import json as _json
    with open(os.path.join(latest_checkpoint(str(tmp_path)),
                           "manifest.json")) as f:
        leaves = _json.load(f)["leaves"]
    w = next(e for e in leaves if e["path"].endswith("w"))
    assert w["codec"] == P16_1.name and w["stored_dtype"] == "uint16"
    assert w["nbytes"] == 8 * 4 * 2, w  # half of the float32 payload


def test_checkpoint_atomicity_crash_sim(tmp_path):
    """A .tmp leftover (simulated crash) must be invisible + collectable."""
    tree = _tree()
    save_checkpoint(str(tmp_path), 1, tree)
    crash = tmp_path / "step_00000002.tmp"
    crash.mkdir()
    (crash / "manifest.json").write_text("{corrupt")
    assert latest_checkpoint(str(tmp_path)).endswith("step_00000001")
    assert gc_tmp(str(tmp_path)) == 1
    assert not crash.exists()


def test_checkpoint_async_manager_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = _tree()
    for step in (1, 2, 3, 4):
        mgr.save_async(step, tree, extra={"next_step": step})
    mgr.wait()
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert steps == ["step_00000003", "step_00000004"], steps
    mgr.close()


def test_checkpoint_elastic_resharding(tmp_path):
    """Save under one layout, restore under another: values identical."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    save_checkpoint(str(tmp_path), 1, tree)
    mesh1 = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    sh = {"w": NamedSharding(mesh1, P("data", None))}
    restored, _ = load_checkpoint(str(tmp_path), tree, shardings=sh)
    assert (np.asarray(restored["w"]) == np.asarray(tree["w"])).all()
    assert restored["w"].sharding == sh["w"]


# ------------------------------------------------------------------- FT -------
def test_with_retries():
    calls = []
    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return "ok"
    assert with_retries(flaky, retries=5, base_delay=0.001) == "ok"
    assert len(calls) == 3
    with pytest.raises(ValueError):
        with_retries(lambda: (_ for _ in ()).throw(ValueError()), retries=2,
                     base_delay=0.001)


def test_straggler_monitor():
    m = StragglerMonitor(threshold=3.0)
    assert not m.observe(1.0)
    for _ in range(5):
        assert not m.observe(1.1)
    assert m.observe(10.0)       # 10x the EWMA -> straggler
    assert m.events == 1
    assert not m.observe(1.0)    # baseline not polluted by the outlier


def test_ft_loop_preemption_and_resume(tmp_path):
    """Preempt mid-run, then resume from the forced checkpoint."""
    mgr = CheckpointManager(str(tmp_path), keep=3)
    sig = PreemptionSignal()
    loop = FaultTolerantLoop(ckpt=mgr, save_every=100, preemption=sig)

    def step_fn(state, step):
        if step == 4:
            sig.preempt()
        return {"x": state["x"] + 1}

    state, next_step = loop.run({"x": jnp.asarray(0)}, step_fn,
                                start_step=0, num_steps=100)
    assert next_step == 5 and int(state["x"]) == 5
    mgr.wait()

    mgr2 = CheckpointManager(str(tmp_path), keep=3)
    loop2 = FaultTolerantLoop(ckpt=mgr2, save_every=100)
    state2, start = loop2.resume({"x": jnp.asarray(0)})
    assert start == 5 and int(state2["x"]) == 5
    state3, nxt = loop2.run(state2, lambda s, i: {"x": s["x"] + 1},
                            start_step=start, num_steps=3)
    assert nxt == 8 and int(state3["x"]) == 8
    mgr.close(); mgr2.close()
