"""Substrate tests: optimizer, data pipeline, checkpointing, compression,
serving engine, trainer fault tolerance + straggler rerank."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import AsyncCheckpointer, latest_step, restore, save
from repro.configs import get_config
from repro.data import SyntheticLM, host_batch
from repro.models import get_model
from repro.optim import (
    AdamWConfig,
    compress_grads,
    cosine_schedule,
    decompress_grads,
    error_feedback_update,
    global_norm,
)
from repro.serve import GenerationConfig, GenerationEngine
from repro.train import (
    ClusterView,
    Trainer,
    TrainerConfig,
    init_state,
    make_train_step,
)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_reduces_quadratic():
    from repro.optim import apply_opt, init_opt

    cfg = AdamWConfig(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = init_opt(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, _ = apply_opt(cfg, params, grads, state)
    assert float(jnp.abs(params["w"]).max()) < 0.1


def test_grad_clipping_bounds_update():
    from repro.optim import apply_opt, init_opt

    cfg = AdamWConfig(lr=1.0, clip_norm=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    state = init_opt(params)
    _, _, metrics = apply_opt(cfg, params, {"w": jnp.full(4, 1e6)}, state)
    assert metrics["grad_norm"] > 1e5  # raw norm reported


def test_cosine_schedule_shape():
    fn = cosine_schedule(1.0, warmup=10, total=100)
    assert float(fn(jnp.asarray(0))) == pytest.approx(0.0)
    assert float(fn(jnp.asarray(10))) == pytest.approx(1.0, abs=0.01)
    assert float(fn(jnp.asarray(100))) == pytest.approx(0.1, abs=0.02)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_compression_error_feedback_converges():
    """With error feedback, repeated compression of a constant gradient
    must deliver the full magnitude on average (residual stays bounded)."""
    g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal(256) * 1e-3)}
    residual = error_feedback_update(g)
    acc = jnp.zeros(256)
    for _ in range(50):
        q, s, residual = compress_grads(g, residual)
        acc = acc + decompress_grads(q, s)["w"]
    np.testing.assert_allclose(np.asarray(acc) / 50, np.asarray(g["w"]),
                               atol=1e-4)
    assert float(jnp.abs(residual["w"]).max()) < 1e-3


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_synthetic_deterministic_and_restart_safe():
    ds = SyntheticLM(1000, 32, 4, seed=7)
    b1 = host_batch(ds, 5)
    b2 = host_batch(ds, 5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])
    # different steps differ
    b3 = host_batch(ds, 6)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_synthetic_learnable_structure():
    ds = SyntheticLM(256, 16, 2, seed=0)
    b = host_batch(ds, 0)
    # deterministic Markov structure: label mostly = 31*t+7 mod V
    t, l = b["tokens"], b["labels"]
    frac = np.mean((31 * t + 7) % 256 == l)
    assert frac > 0.8


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(10), "b": {"c": jnp.ones((3, 3)) * 2}}
    save(str(tmp_path), 42, tree, extras={"note": "x"})
    assert latest_step(str(tmp_path)) == 42
    restored, step, extras = restore(str(tmp_path), tree)
    assert step == 42 and extras["note"] == "x"
    np.testing.assert_array_equal(restored["a"], np.arange(10))
    np.testing.assert_array_equal(restored["b"]["c"], np.ones((3, 3)) * 2)


def test_checkpoint_latest_pointer_survives_multiple_saves(tmp_path):
    tree = {"a": jnp.zeros(2)}
    save(str(tmp_path), 1, tree)
    save(str(tmp_path), 2, {"a": jnp.ones(2)})
    restored, step, _ = restore(str(tmp_path), tree)
    assert step == 2
    np.testing.assert_array_equal(restored["a"], np.ones(2))


def test_async_checkpointer(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path))
    ck.save(7, {"w": jnp.full(4, 3.0)})
    ck.wait()
    restored, step, _ = restore(str(tmp_path), {"w": jnp.zeros(4)})
    assert step == 7
    np.testing.assert_array_equal(restored["w"], np.full(4, 3.0))


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------

def test_engine_greedy_matches_manual_decode():
    cfg = get_config("qwen2-0.5b").smoke()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = GenerationEngine(model, params,
                           GenerationConfig(max_new_tokens=5, eos_token=-1))
    prompts = [[1, 2, 3, 4], [4, 3, 2, 1]]
    outs = eng.generate(prompts)
    assert len(outs) == 2 and all(len(o) == 5 for o in outs)
    # manual: prefill + argmax chain must match engine output
    logits, cache = jax.jit(model.prefill)(params, jnp.asarray(prompts))
    from repro.serve.engine import _grow_cache

    cache = _grow_cache(cache, 4, 9)
    cur = jnp.argmax(logits, -1).astype(jnp.int32)
    manual = [np.asarray(cur)]
    for _ in range(4):
        logits, cache = jax.jit(model.decode_step)(params, cur, cache)
        cur = jnp.argmax(logits, -1).astype(jnp.int32)
        manual.append(np.asarray(cur))
    manual = np.stack(manual, 1)
    np.testing.assert_array_equal(np.asarray(outs), manual)


# ---------------------------------------------------------------------------
# trainer: fault tolerance + elastic restart + straggler rerank
# ---------------------------------------------------------------------------

def _mini_trainer(tmp_path, failure_injector=None, total=12):
    from repro.core import make_datacenter

    cfg = get_config("qwen2-0.5b").smoke()
    model = get_model(cfg)
    state = init_state(model, jax.random.PRNGKey(0))
    step_fn = jax.jit(make_train_step(model, AdamWConfig(lr=1e-3)))
    ds = SyntheticLM(cfg.vocab_size, 16, 4, seed=0)

    def batches():
        i = 0
        while True:
            yield host_batch(ds, i)
            i += 1

    cluster = ClusterView(
        fabric=make_datacenter(16, seed=0),
        mesh_shape=(4, 4), axis_names=("data", "model"))
    return Trainer(
        step_fn=step_fn, state=state, batches=batches(),
        cfg=TrainerConfig(total_steps=total, ckpt_every=4,
                          ckpt_dir=str(tmp_path), log_every=2),
        cluster=cluster, failure_injector=failure_injector)


def test_trainer_runs_and_checkpoints(tmp_path):
    tr = _mini_trainer(tmp_path)
    report = tr.run()
    assert report["final_step"] == 12
    assert latest_step(str(tmp_path)) == 12
    assert report["restarts"] == 0


def test_trainer_elastic_restart_on_failure(tmp_path):
    fired = {"done": False}

    def injector(step):
        if step == 6 and not fired["done"]:
            fired["done"] = True
            return [3, 7]          # two nodes die
        return None

    tr = _mini_trainer(tmp_path, failure_injector=injector)
    report = tr.run()
    assert report["restarts"] == 1
    assert report["final_step"] == 12
    # cluster shrank and re-planned: mesh fits survivors, active nodes
    # are a survivor subset, plan covers every mesh slot
    assert len(tr.cluster.alive) == 14
    mesh_n = int(np.prod(tr.cluster.mesh_shape))
    assert mesh_n <= 14
    assert set(tr.cluster.active) <= set(tr.cluster.alive)
    assert len(tr.cluster.active) == mesh_n
    assert sorted(tr.cluster.plan.flat.tolist()) == list(range(mesh_n))


def test_trainer_resumes_from_checkpoint_not_zero(tmp_path):
    """After a failure at step 6 with ckpt_every=4, training resumes from
    step 4 (the last durable checkpoint), not from scratch."""
    seen_steps = []

    def injector(step):
        seen_steps.append(step)
        if step == 6 and seen_steps.count(6) == 1:
            return [0]
        return None

    tr = _mini_trainer(tmp_path, failure_injector=injector)
    report = tr.run()
    assert report["final_step"] == 12
    # step 6 encountered twice: once pre-failure, once after restore to 4
    assert seen_steps.count(6) == 2


def test_trainer_stops_on_non_finite_loss(tmp_path):
    """A diverged step raises instead of training on: the launcher exits
    non-zero rather than reporting NaN losses."""
    tr = _mini_trainer(tmp_path, total=3)
    good = tr.step_fn

    def nan_at_2(state, batch):
        state, metrics = good(state, batch)
        if int(state.step) == 2:
            metrics = dict(metrics, loss=jnp.float32(np.nan))
        return state, metrics

    tr.step_fn = nan_at_2
    with pytest.raises(FloatingPointError, match="non-finite loss nan at "
                                                 "step 2"):
        tr.run()


def test_trainer_without_checkpoint_dir_writes_nothing(tmp_path,
                                                       monkeypatch):
    monkeypatch.chdir(tmp_path)
    tr = _mini_trainer(tmp_path / "unused", total=4)
    tr = Trainer(step_fn=tr.step_fn, state=tr.state, batches=tr.batches,
                 cfg=TrainerConfig(total_steps=4, ckpt_dir=None,
                                   log_every=2))
    report = tr.run()
    assert report["final_step"] == 4
    assert tr.ckpt is None
    assert not list(tmp_path.iterdir())
