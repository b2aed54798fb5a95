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
    from repro.kernels.schedule_runner import schedule_body
    from repro.train.overlap_grads import certified_allreduce

    # this process's backend is the CPU; on the chip the runner compiles
    # its Pallas add, so compile it that way here
    monkeypatch.setattr(ring_collective, "on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices), ("data",))
    sched = certified_allreduce(4, 1 << 20, perm=[2, 0, 3, 1])
    chunk_len = (1 << 18) // sched.n_chunks          # 1 MiB of f32
    buf = _sds((4, sched.n_chunks + 1, chunk_len), jnp.float32,
               NamedSharding(mesh, P("data")))
    text = jax.jit(schedule_body(mesh, "data", sched)).lower(buf) \
        .compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
