"""Training cells: set-up, the measured window and the output check.

The entry the window drives is ``repro.train.Trainer.run`` over the step
``repro.launch.train.train_on_mesh`` builds, composed here from the same
calls: ``build_mesh`` (Session attach -> plan -> apply, and on several
chips ``Session.overlap_step``), ``configure_sp``, ``jit_train_step``
with the state and batch shardings, the state made straight into those
shardings, and a feed that places every batch on the mesh.

Set-up builds one object, the compiled step with its state, drives it
through the first ``check_steps`` steps with the window's own call and
feed, reads what the output check compares, and hands the same trainer
to the window.  After the window the program's state is freed and the
plain reference (``bench/reference/<family>.py``) follows the same steps
from the same seed.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import statistics
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import data, spec


# ---------------------------------------------------------------------------
# host spans, on the profiler's clock when a trace is taken
# ---------------------------------------------------------------------------

class Spans:
    """Named host spans: seconds by name, and profiler annotations."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        cm = (jax.profiler.TraceAnnotation(f"bench.{name}") if self.annotate
              else contextlib.nullcontext())
        t = time.perf_counter()
        with cm:
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) + \
            time.perf_counter() - t


# ---------------------------------------------------------------------------
# the system under test, built as train_on_mesh builds it
# ---------------------------------------------------------------------------

def model_config(cfg: Dict[str, Any]):
    """The repo's registry entry with the file's sizes put in."""
    from repro.configs import get_config

    arch = dataclasses.replace(get_config(cfg["name"]), **cfg["model"])
    for k, v in cfg["model"].items():
        if getattr(arch, k) != v:
            raise spec.SpecError(f"{cfg['name']}: {k} is {getattr(arch, k)}"
                                 f" after the cut, the file says {v}")
    return arch


def optimizer(opt: Dict[str, Any]):
    from repro.optim import AdamWConfig, cosine_schedule

    return AdamWConfig(
        b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"], clip_norm=opt["clip_norm"],
        schedule=cosine_schedule(opt["lr"], opt["warmup_steps"],
                                 opt["total_steps"], opt["floor"]))


def state_maker(cfg: Dict[str, Any], ref_mod):
    """``seed -> TrainState``: the seeded weights in the program's state."""
    import jax
    import jax.numpy as jnp
    from repro.optim import init_opt
    from repro.train.train_step import TrainState

    def make(key):
        params = ref_mod.make_params(cfg["model"], key)
        return TrainState(params=params, opt=init_opt(params),
                          step=jnp.zeros((), jnp.int32))
    return make


def build_step(arch, mesh, opt_cfg, rows: int, seq: int, make_state,
               reducer=None):
    """The jitted step and its shardings, as ``train_on_mesh`` makes them.

    Returns ``(step_fn, state_shapes, batch_shapes, state_ns, batch_ns)``.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import get_model
    from repro.train.train_step import (
        batch_pspecs, jit_train_step, state_pspecs)

    model = get_model(arch)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
    state_shapes = jax.eval_shape(make_state, key)
    program = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if jax.tree.structure(program) != jax.tree.structure(state_shapes.params) \
            or any(a.shape != b.shape or a.dtype != b.dtype for a, b in zip(
                jax.tree.leaves(program), jax.tree.leaves(state_shapes.params))):
        raise spec.SpecError(f"{arch.name}: the seeded weights do not have "
                             f"the program's parameter layout")
    tokens = jax.ShapeDtypeStruct((rows, seq), jnp.int32)
    batch_shapes = {"tokens": tokens, "labels": tokens}
    if reducer is None:
        def named(tree):
            return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                is_leaf=lambda x: isinstance(x, P))
        state_ns = named(state_pspecs(state_shapes, arch, mesh))
        batch_ns = named(batch_pspecs(batch_shapes, mesh))
        step_fn = jit_train_step(model, opt_cfg, arch, mesh, state_shapes,
                                 batch_shapes)
    else:
        state_ns = jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                state_shapes)
        batch_ns = {k: NamedSharding(mesh, P(reducer.axis))
                    for k in batch_shapes}
        step_fn = jit_train_step(model, opt_cfg, arch, mesh, state_shapes,
                                 batch_shapes, overlap=reducer.mode,
                                 reducer=reducer, axis=reducer.axis)
    return step_fn, state_shapes, batch_shapes, state_ns, batch_ns


def session_args(traffic: Dict[str, Any]) -> argparse.Namespace:
    """``build_mesh``'s arguments as ``repro train`` passes them; a plan
    cache sits at a fixed path inside the checkout."""
    s = traffic["session"]
    return argparse.Namespace(
        mesh=s["mesh"], reorder=s["reorder"], payload_bytes=None,
        plan_cache_dir=str(spec.ROOT / s["plan_cache"])
        if s.get("plan_cache") else None)


def session_config(traffic: Dict[str, Any], grad_bytes: float):
    from repro.session import SessionConfig

    return SessionConfig.from_dict({
        "workload": "train", "payload_bytes": grad_bytes,
        "overlap": traffic["session"]["overlap"]})


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------

class Feed:
    """The window's batches: drawn from the seed, placed on the mesh,
    ended by a deadline once one is set."""

    def __init__(self, seed, rows, seq, vocab, sharding, spans, keep: int):
        self.seed, self.rows, self.seq, self.vocab = seed, rows, seq, vocab
        self.sharding, self.spans, self.keep = sharding, spans, keep
        self.deadline: Optional[float] = None
        self.kept: List[Dict[str, np.ndarray]] = []
        self.served = 0

    def __iter__(self):
        import jax

        while self.deadline is None or time.perf_counter() < self.deadline:
            with self.spans("batch"):
                b = data.batch(self.seed, self.served, self.rows, self.seq,
                               self.vocab)
                dev = jax.device_put(b, self.sharding)
            if self.served < self.keep:
                self.kept.append(b)
            self.served += 1
            yield dev


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, t0: float,
        log: Callable[[str], None],
        limits: Optional[Dict[str, float]] = None) -> Dict[str, Any]:
    """One run of a training cell; ``limits`` replaces the cell's own
    (``bench/limits/<cell>.json``) where given."""
    import jax

    from repro.launch.specs import configure_sp
    from repro.launch.train import build_mesh
    from repro.train import Trainer, TrainerConfig

    cfg, traffic = cell.config, cell.traffic
    ref_mod = spec.reference(cfg["family"])
    arch = model_config(cfg)
    n_dev = len(jax.devices())
    rows = traffic["batch_per_chip"] * n_dev
    seq = traffic["seq"]
    opt = traffic["optimizer"]
    check_steps = traffic["check_steps"]
    spans = Spans(annotate=trace)
    make_state = state_maker(cfg, ref_mod)

    with spans("setup.plan"):
        shapes = jax.eval_shape(make_state, jax.random.key(0))
        grad_bytes = float(sum(s.size * s.dtype.itemsize
                               for s in jax.tree.leaves(shapes.params)))
        mesh, plan, reducer = build_mesh(
            session_args(traffic), n_dev,
            session_config=session_config(traffic, grad_bytes),
            grad_bytes=grad_bytes)
        configure_sp(arch, mesh, plan=plan)
    if reducer is not None:
        log(f"reducer: {reducer.schedule.algorithm} over {reducer.n} chips, "
            f"{len(reducer.schedule.rounds)} rounds, bucket "
            f"{reducer.bucket_bytes:.0f} B, mode {reducer.mode}, pallas add "
            f"{reducer.use_pallas_add}, rank order "
            f"{list(reducer.schedule.rank_of)}")
    step_fn, state_shapes, batch_shapes, state_ns, batch_ns = build_step(
        arch, mesh, optimizer(opt), rows, seq, make_state, reducer)

    with spans("setup.init"):
        state = jax.jit(make_state, out_shardings=state_ns)(
            ref_mod.seed_key(seed))
        jax.block_until_ready(state)
    with spans("setup.compile"), jax.set_mesh(mesh):
        sds = lambda s, ns: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=ns)
        compiled = step_fn.lower(
            jax.tree.map(sds, state_shapes, state_ns),
            jax.tree.map(sds, batch_shapes, batch_ns)).compile()
    step = compiled
    if trace:
        def step(s, b, inner=compiled):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                return inner(s, b)

    feed = Feed(seed, rows, seq, arch.vocab_size, batch_ns, spans,
                keep=check_steps)
    trainer = Trainer(step_fn=step, state=state, batches=iter(feed),
                      cfg=TrainerConfig(total_steps=1, ckpt_dir=None,
                                        log_every=1))
    del state

    # the output check's first steps, through the window's call and feed
    b1 = opt["b1"]
    with spans("setup.check"), jax.set_mesh(mesh):
        trainer.run()
        m_norms = jax.jit(partial(ref_mod.leaf_norms, scale=1.0 / (1 - b1)))
        got_grad = {k: float(v) for k, v in
                    m_norms(trainer.state.opt.m).items()}
        trainer.cfg.total_steps = check_steps
        trainer.run()
        got_change = change_norms(ref_mod, cfg["model"], seed,
                                  trainer.state.params)
        got_losses = [r["loss"] for r in trainer.history[:check_steps]]
    trainer.cfg.log_every = 1 << 30
    setup_s = time.perf_counter() - t0

    # the measured window
    trace_dir = None
    if trace:
        import tempfile
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
    first = feed.served
    t_start = time.perf_counter()
    feed.deadline = t_start + seconds
    with spans("window"), jax.set_mesh(mesh):
        trainer.cfg.total_steps = 1 << 62
        try:
            trainer.run()
        except StopIteration:
            pass
    t_end = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    steps = feed.served - first
    window_s = t_end - t_start
    tokens = steps * rows * seq
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in mesh.devices.flat)
    log(f"window: {steps} steps of {rows} x {seq} tokens in {window_s:.3f} s; "
        f"peak_bytes_in_use {peak}")

    hlo = compiled.as_text() if trace else None
    # the program's state goes before the reference comes
    for x in jax.tree.leaves(trainer.state):
        x.delete()
    trainer.state = None
    del compiled, step, trainer

    t_ref = time.perf_counter()
    ref_mesh = jax.make_mesh((n_dev,), ("r",))
    batches = [(b["tokens"], b["labels"]) for b in feed.kept]
    want = ref_mod.train(cfg["model"], opt, ref_mesh, seed, batches)
    log(f"reference: {len(batches)} steps in "
        f"{time.perf_counter() - t_ref:.1f} s")
    checked = compare(
        {"losses": got_losses, "grad": got_grad, "change": got_change}, want,
        limits if limits is not None else spec.limits(cell.name))

    result = {
        "steps": steps, "window_s": window_s, "tokens": tokens,
        "setup_s": setup_s, "peak_bytes": peak, "spans": spans.seconds,
        "checked": checked, "trace_dir": trace_dir,
        "flops_per_token": spec.flops(cfg["family"]).train_flops_per_token(
            cfg["model"], seq),
        "chips": n_dev, "hlo": hlo,
    }
    return result


def change_norms(ref_mod, model_cfg, seed, params) -> Dict[str, float]:
    """Per-leaf norms of the program's parameters' change since the
    seed, the seeded values made again leaf by leaf beside them."""
    import jax
    import jax.numpy as jnp

    key = ref_mod.seed_key(seed)
    dt = jnp.dtype(model_cfg["dtype"])
    flat = ref_mod.flatten(params)
    out: Dict[str, float] = {}
    for path, (shape, init) in ref_mod.leaf_table(model_cfg).items():
        f = jax.jit(lambda p, k, path=path, shape=shape, init=init:
                    ref_mod.leaf_norms({path: p - ref_mod.make_leaf(
                        k, path, shape, init, dt)}))
        out.update({k: float(v) for k, v in f(flat[path], key).items()})
    return out


# ---------------------------------------------------------------------------
# the output check
# ---------------------------------------------------------------------------

def leaf_gap(got: Dict[str, float], want: Dict[str, float],
             keys: List[str]) -> float:
    """Worst leaf's |norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    med = statistics.median(want[k] for k in keys)
    return max(abs(got[k] - want[k]) / max(want[k], med) for k in keys)


def compare(got: Dict[str, Any], want: Dict[str, Any],
            limits: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """The numbers compared, each with its limit.

    Leaves whose reference gradient is under a thousandth of the median
    leaf's move under AdamW by round-off alone; they are left out of the
    change (and counted in ``change_leaves``)."""
    raw = want["grad_raw"]
    med = statistics.median(raw.values())
    moved = sorted(k for k, g in raw.items() if g >= 1e-3 * med)
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(got["losses"], want["losses"]))
    if len(got["losses"]) != len(want["losses"]):
        loss_gap = float("inf")
    values = {
        "loss_gap": loss_gap,
        "grad_gap": leaf_gap(got["grad"], want["grad"], sorted(raw)),
        "change_gap": leaf_gap(got["change"], want["change"], moved),
    }
    return {k: {"value": v, "limit": float(limits[k])}
            for k, v in values.items()}
