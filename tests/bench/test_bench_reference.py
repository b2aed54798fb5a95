"""The benchmark's yardstick for dense models: the model FLOP count by
hand for both configurations, and the plain float32 reference against
``repro.models`` and the program's AdamW on the CPU at smoke size, for
a tied (qwen2 layout) and an untied (granite layout) configuration, on
the same seeded weights."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import data, spec  # noqa: E402


def _model(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())["model"]


def test_flops_by_hand():
    f = spec.flops("dense")
    # qwen2-0.5b: per layer 896*896*2 (q, o) + 2*896*128 (k, v)
    # + 3*896*4864 (SwiGLU) = 14,909,440; x 24 + the tied head
    # 896*151,936 = 493,961,216 matmul weights.  Causal attention at
    # 2048: 24 layers * 4*14*64 * 2049/2 = 88,123,392.
    import dataclasses

    from repro.configs import get_config

    q = dataclasses.asdict(get_config("qwen2-0.5b"))
    assert f.matmul_params(q) == 493_961_216
    assert f.attention_flops(q, 2048) == 88_123_392
    assert f.train_flops_per_token(q, 2048) == 3 * (
        2 * 493_961_216 + 88_123_392) == 3_228_137_472
    # granite-8b cut to 3 layers: per layer 4096*4096*2 + 2*4096*1024
    # + 3*4096*14336 = 218,103,808; x 3 + head 4096*49,152; attention
    # at 4096: 3 * 4*32*128 * 4097/2 = 100,687,872.
    g = _model("granite-8b")
    assert g["n_layers"] == 3
    assert f.matmul_params(g) == 3 * 218_103_808 + 201_326_592
    assert f.attention_flops(g, 4096) == 100_687_872
    assert f.train_flops_per_token(g, 4096) == 5_435_891_712


OPT = {"lr": 1e-3, "warmup_steps": 10, "total_steps": 1000, "floor": 0.1,
       "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "clip_norm": 1.0}


def _tiny(name, tied):
    return {"name": name, "family": "dense", "model": {
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab_size": 256, "norm_eps": 1e-6,
        "rope_theta": 1e4, "qkv_bias": tied, "tie_embeddings": tied,
        "dtype": "float32", "attention_impl": "xla", "remat": "block",
        "attn_q_chunk": 16, "loss_chunk_size": 16}}


@pytest.mark.parametrize("name,tied", [("qwen2-0.5b", True),
                                       ("granite-8b", False)])
def test_reference_matches_the_program(name, tied):
    import jax
    import jax.numpy as jnp

    from bench.kinds import train as K
    from repro.models import get_model
    from repro.optim import init_opt
    from repro.train.train_step import TrainState, make_train_step

    ref = spec.reference("dense")
    cfg = _tiny(name, tied)
    arch = K.model_config(cfg)
    model = get_model(arch)
    seed = 2**33 + 5
    params = jax.jit(lambda k: ref.make_params(cfg["model"], k))(
        ref.seed_key(seed))
    b = data.batch(seed, 0, 2, 64, cfg["model"]["vocab_size"])

    loss, grads = jax.value_and_grad(model.loss)(params, b)
    step = jax.jit(make_train_step(model, K.optimizer(OPT)))
    state, _ = step(TrainState(params, init_opt(params),
                               jnp.zeros((), jnp.int32)), b)

    r = ref.Reference(cfg["model"], OPT, jax.make_mesh((1,), ("r",)), seed)
    got = r.step(b["tokens"], b["labels"])
    assert got["loss"] == pytest.approx(float(loss), rel=1e-5)
    want = {k: float(v) for k, v in ref.leaf_norms(grads).items()}
    assert set(got["grad_norms"]) == set(want)
    for k, v in want.items():
        assert got["grad_norms"][k] == pytest.approx(v, rel=1e-4, abs=1e-7)
    # AdamW's first update is lr * g / (|g| + eps): exactly +-lr (1e-4
    # here) unless |g| is near eps, where float32 rounding of the
    # gradient moves it by a fraction of lr; 1e-5 allows a tenth of it
    flat = ref.flatten(state.params)
    for k, v in r.p.items():
        np.testing.assert_allclose(np.asarray(v), np.asarray(flat[k]),
                                   rtol=1e-5, atol=1e-5)
