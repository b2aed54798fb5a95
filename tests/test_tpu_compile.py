"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

The ``v5e:2x2`` topology is described, not attached: JAX's installed TPU
compiler compiles for it and refuses what the chip would refuse
(unaligned tiles, primitives Mosaic cannot lower), which interpret-mode
tests cannot show.  Nothing runs, so these tests say nothing about
results or speed.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and each
test worker imports every test file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for described devices is written to the persistent cache
    # but cannot be read back without a chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_add_compiles_at_bucket_width(one_chip, dtype):
    from repro.kernels.ring_collective import fused_add

    # one planned 1 MiB gradient bucket per rank, as [rows, chunk_len]
    x = _sds((4, 1 << 17), dtype, one_chip)
    _assert_kernel(jax.jit(lambda a, b: fused_add(a, b)).lower(x, x)
                   .compile())


def _wkv_inputs(one_chip, seq=256):
    cfg = get_config("rwkv6-1.6b")
    H, K = cfg.d_model // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    x = _sds((1, seq, H, K), jnp.bfloat16, one_chip)
    return x, x, x, x, _sds((H, K), jnp.bfloat16, one_chip)


@pytest.mark.parametrize("kernel", ["chunked_matmul", "scan"])
def test_wkv_kernels_compile_at_rwkv6_widths(one_chip, kernel):
    from repro.kernels.rwkv6_chunked import wkv_chunked_matmul
    from repro.kernels.rwkv6_scan import wkv_scan

    fn = {"chunked_matmul": lambda *a: wkv_chunked_matmul(*a, chunk=16),
          "scan": lambda *a: wkv_scan(*a, chunk=64)}[kernel]
    _assert_kernel(jax.jit(fn).lower(*_wkv_inputs(one_chip)).compile())


def test_flash_attention_compiles_at_qwen2_widths(one_chip):
    from repro.kernels.flash_attention import flash_attention

    cfg = get_config("qwen2-0.5b")
    q = _sds((1, cfg.n_heads, 2048, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _sds((1, cfg.n_kv_heads, 2048, cfg.head_dim), jnp.bfloat16,
              one_chip)
    _assert_kernel(jax.jit(lambda q, k, v: flash_attention(q, k, v))
                   .lower(q, kv, kv).compile())


def test_certified_allreduce_body_compiles_on_four_chips(topo, monkeypatch):
    from repro.kernels import ring_collective
    from repro.kernels.schedule_runner import schedule_body, slab_shape
    from repro.train.overlap_grads import certified_allreduce

    # this process's backend is the CPU; on the chip the runner compiles
    # its Pallas add, so compile it that way here
    monkeypatch.setattr(ring_collective, "on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices), ("data",))
    sched = certified_allreduce(4, 1 << 20, perm=[2, 0, 3, 1])
    chunk_len = (1 << 18) // sched.n_chunks          # 1 MiB of f32
    buf = _sds((4, sched.n_chunks) + slab_shape(chunk_len), jnp.float32,
               NamedSharding(mesh, P("data")))
    text = jax.jit(schedule_body(mesh, "data", sched)).lower(buf) \
        .compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


def test_bucketed_step_carries_certified_scopes(topo):
    """The four-chip bucketed train step, lowered for v5e: its ops carry
    the model's and the certified reducer's scopes in the locations the
    compiler turns into op-name metadata, the reducer's gauge holds the
    number of buckets it split the gradient into, and the program issues
    (rounds x chunk_factor + 2) ``ppermute``s per bucket: each round's
    transfer, the seeding move and the move back to rank order."""
    import re

    from repro import obs
    from repro.models import get_model
    from repro.optim import AdamWConfig
    from repro.train import init_state, jit_train_step
    from repro.train.overlap_grads import (OverlapGradReducer,
                                           certified_allreduce,
                                           partition_tree)

    cfg = get_config("qwen2-0.5b").smoke()
    model = get_model(cfg)
    mesh = Mesh(np.array(topo.devices), ("data",))
    state = jax.eval_shape(lambda k: init_state(model, k),
                           jax.random.PRNGKey(0))
    bucket = 1 << 16
    sched = certified_allreduce(4, bucket, perm=[2, 0, 3, 1])
    reducer = OverlapGradReducer(mesh, "data", sched, bucket_bytes=bucket)
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    state = jax.tree.map(lambda s: _sds(s.shape, s.dtype, rep), state)
    tokens = _sds((8, 32), jnp.int32, rows)
    prev = obs.set_metrics(obs.MetricsRegistry())
    try:
        lowered = jit_train_step(
            model, AdamWConfig(), cfg, mesh, None, None, overlap="bucketed",
            reducer=reducer).lower(state, {"tokens": tokens,
                                           "labels": tokens})
        gauges = obs.metrics().snapshot()["gauges"]
    finally:
        obs.set_metrics(prev)
    buckets = len(partition_tree(state.params, bucket))
    assert buckets > 1
    assert gauges["train.overlap.buckets"] == buckets
    permutes = lowered.as_text().count("stablehlo.collective_permute")
    assert permutes == buckets * (len(sched.rounds)
                                  * max(1, sched.chunk_factor) + 2)
    text = lowered.as_text(debug_info=True)
    for scope in ("certified.permute", "certified.table", "certified.add",
                  "certified.pack", "certified.finish", "attention", "mlp",
                  "loss", "optimizer"):
        # jvp(loss): value_and_grad wraps a scope at the top of the loss
        assert f"{scope}/" in text or f"({scope})" in text, scope
