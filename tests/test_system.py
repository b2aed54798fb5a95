"""End-to-end behaviour tests for the paper's system.

The headline invariant (the paper's thesis): starting from the random
order the cloud hands you, the full pipeline — probe -> cost model ->
solve -> reorder — must produce an ordering that is faster *when actually
executed* (simulated with contention), across fabrics and seeds; and the
whole thing must survive training-loop integration (reordered plan +
checkpoint/restart + rerank) without touching model code.
"""

import dataclasses

import jax
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - fallback when dev deps absent
    from _hypothesis_shim import given, settings, st

from repro.core import (
    CollectiveSimulator,
    cost_matrix,
    make_cost_model,
    make_datacenter,
    make_tpu_fleet,
    optimize_mesh_assignment,
    optimize_rank_order,
    probe_fabric,
    scramble,
    solve_worst,
)


@given(st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_pipeline_beats_random_orders_property(seed):
    """Property (hypothesis): on any generated fabric, the solved order's
    simulated time <= the mean of random orders.  This is the system's
    contract; it must hold regardless of topology seed."""
    fab, _ = scramble(make_datacenter(32, seed=seed), seed=seed + 1)
    c = cost_matrix(probe_fabric(fab, seed=seed + 2))
    res = optimize_rank_order(c, "ring", method="paper", iters=400, seed=0)
    sim = CollectiveSimulator(fab, "ring", 50e6)
    rng = np.random.default_rng(seed)
    t_solved = sim.run(res.perm)
    t_rand = sim.run_many([rng.permutation(32) for _ in range(8)])
    assert t_solved <= t_rand.mean() * 1.02


def test_reordered_mesh_is_transparent_to_the_model():
    """The paper's non-intrusiveness claim, JAX edition: the same jitted
    train step runs identically (same loss) on an identity-ordered and a
    reordered mesh — reordering changes only device placement."""
    from repro.configs import get_config
    from repro.data import SyntheticLM, host_batch
    from repro.launch.mesh import make_mesh_for_tests, make_reordered_mesh
    from repro.models import get_model
    from repro.optim import AdamWConfig
    from repro.train import init_state, make_train_step

    cfg = get_config("qwen2-0.5b").smoke()
    model = get_model(cfg)
    state = init_state(model, jax.random.PRNGKey(0))
    ds = SyntheticLM(cfg.vocab_size, 16, 4, seed=0)
    batch = host_batch(ds, 0)
    step = jax.jit(make_train_step(model, AdamWConfig(lr=1e-3)))

    # 1-device process: both meshes are (1, 1); the reordered one goes
    # through the MeshPlan -> device-permutation code path.
    fleet = make_tpu_fleet(n_pods=1, pod_shape=(1, 1), seed=0)
    c = cost_matrix(probe_fabric(fleet, seed=1))
    plan = optimize_mesh_assignment(c, (1, 1), ("data", "model"))
    mesh_r = make_reordered_mesh(plan)
    mesh_i = make_mesh_for_tests((1, 1), ("data", "model"))

    with jax.set_mesh(mesh_i):
        _, m1 = step(state, batch)
    with jax.set_mesh(mesh_r):
        _, m2 = step(state, batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-6)


def test_fleet_reorder_recovers_pod_structure():
    """On a scrambled 2-pod fleet the hierarchical mesh plan should place
    the DCN boundary on the pod axis: chips within one solved pod group
    should overwhelmingly come from one physical pod."""
    fleet = make_tpu_fleet(n_pods=2, pod_shape=(4, 4), seed=3)
    scr, hidden = scramble(fleet, seed=4)
    c = cost_matrix(probe_fabric(scr, seed=5), 4e6)
    plan = optimize_mesh_assignment(c, (2, 4, 4), ("pod", "data", "model"))
    # map solved ids back to true pod ids
    true_pod = hidden[plan.assignment.reshape(2, -1)] // 16
    purity = max(
        (true_pod[0] == 0).mean() + (true_pod[1] == 1).mean(),
        (true_pod[0] == 1).mean() + (true_pod[1] == 0).mean()) / 2
    assert purity > 0.9, f"pod purity {purity}"


def test_dryrun_cell_small_mesh():
    """The dry-run machinery end to end on a 1-device mesh: lower +
    compile + roofline artifact for a smoke config."""
    from repro.configs import SHAPES
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_mesh_for_tests
    from repro.launch.specs import input_specs, step_callable
    from repro.configs import get_config
    from repro.launch import hlo_analysis as ha

    cfg = dataclasses.replace(get_config("qwen2-0.5b").smoke(), use_scan=True)
    shape = ShapeSpec("tiny_train", 16, 4, "train")
    mesh = make_mesh_for_tests((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        lowered = jax.jit(step_callable(cfg, shape)).lower(
            *input_specs(cfg, shape, mesh))
        compiled = lowered.compile()
    assert compiled.cost_analysis().get("flops", 0) > 0
    stats = ha.parse_collectives(compiled.as_text())
    assert stats.total_bytes >= 0  # 1-device: no collectives expected
    terms = ha.roofline_terms(1e12, 1e10, 1e8, 256)
    assert terms["dominant"] in ("compute", "memory", "collective")


def test_multidevice_ring_and_pipeline_subprocess():
    """Ring collective + pipeline parallelism on 8 host devices (separate
    process so the main test process keeps its single-device jax)."""
    import os
    import subprocess
    import sys

    prog = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.kernels import ring_reduce_scatter
from repro.kernels.ref import ring_reduce_scatter_ref
from repro.parallel import pipeline_forward

mesh = jax.make_mesh((8,), ("stage",), axis_types=(jax.sharding.AxisType.Auto,))
x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 64)), jnp.float32)
out = ring_reduce_scatter(x, mesh, "stage", perm=[0,3,1,7,2,6,4,5])
np.testing.assert_allclose(np.asarray(out), np.asarray(ring_reduce_scatter_ref(x, 8)), atol=1e-4)

# pipeline: 8 stages of y = tanh(x @ w); compare vs sequential
ws = jnp.asarray(np.random.default_rng(1).standard_normal((8, 16, 16)) * 0.3, jnp.float32)
xs = jnp.asarray(np.random.default_rng(2).standard_normal((4, 2, 16)), jnp.float32)
def stage_fn(w, x): return jnp.tanh(x @ w)
with jax.set_mesh(mesh):
    y = pipeline_forward(stage_fn, ws, xs, mesh, axis="stage")
ref = xs
for i in range(8):
    ref = jnp.tanh(ref @ ws[i])
np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)

# pipeline backward: grads flow through ppermute schedule
def loss(ws):
    return jnp.sum(pipeline_forward(stage_fn, ws, xs, mesh, axis="stage") ** 2)
with jax.set_mesh(mesh):
    g = jax.grad(loss)(ws)
def loss_seq(ws):
    r = xs
    for i in range(8):
        r = jnp.tanh(r @ ws[i])
    return jnp.sum(r ** 2)
g_ref = jax.grad(loss_seq)(ws)
np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-3)
print("MULTIDEVICE OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "MULTIDEVICE OK" in r.stdout
