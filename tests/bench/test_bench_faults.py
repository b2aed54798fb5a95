"""The output check catches a broken timed path.

Each test drives a whole training run of the harness (``run_cell``:
set-up, the first checked steps, a short window, the reference, the
comparison against limits set for this size) at smoke size on the CPU,
skipping only the harness's look for a chip, with one fault planted in
the program underneath:

* a step that returns its state unchanged;
* half of the batch left out, the mean taken over the rest;
* (four host devices) the exchange between chips left out of the
  certified reducer: no ``ppermute`` round runs.

A sound run of the same cell must come out correct, so that a fault's
``correct: false`` is the fault's doing.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

SMALL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 512, "norm_eps": 1e-6,
         "rope_theta": 1e6, "dtype": "bfloat16", "attention_impl": "xla",
         "remat": "block", "attn_q_chunk": 0, "loss_chunk_size": 32}


#: Limits of the check at smoke size, set like the cells' own from
#: readings at this size on the CPU: sound runs of the program over six
#: seeds read at most (loss, grad, change) 6.7e-5, 0.0026, 0.0033 in the
#: granite layout (one device) and 3.4e-5, 0.011, 0.027 in the qwen2
#: layout (four host devices); the float8 control read at least 3.9e-4
#: and 1.1e-4 on the loss.  Narrow widths give each leaf fewer elements
#: to average its rounding over, so these sit above the cells' own.
SMALL_LIMITS = {
    "granite": {"loss_gap": 1.5e-4, "grad_gap": 0.008, "change_gap": 0.05},
    "qwen2": {"loss_gap": 7e-5, "grad_gap": 0.03, "change_gap": 0.1},
}

#: The two layouts at smoke size: granite's (untied head, no bias, one
#: chip, the one-chip cell's traffic) and qwen2's (tied, QKV bias, data
#: parallel over four devices through the certified bucketed reducer).
LAYOUTS = {
    "granite": dict(chips=1, qkv_bias=False, tie_embeddings=False,
                    session={"mesh": "1x1", "reorder": "simulate",
                             "overlap": {"mode": "off"}}),
    "qwen2": dict(chips=4, qkv_bias=True, tie_embeddings=True,
                  session={"mesh": "4", "reorder": "probe",
                           "overlap": {"mode": "bucketed",
                                       "use_pallas_add": False}}),
}


def small_cell(layout):
    """A training cell at smoke size in ``layout``, with the optimizer
    and checked steps of the one-chip cell's traffic."""
    from bench import spec

    lay = LAYOUTS[layout]
    base = spec.cell("granite-8b.train-1chip")
    model = dict(SMALL, qkv_bias=lay["qkv_bias"],
                 tie_embeddings=lay["tie_embeddings"])
    config = {"name": "qwen2-0.5b" if layout == "qwen2" else "granite-8b",
              "family": "dense", "model": model}
    traffic = dict(base.traffic, seq=64, batch_per_chip=2,
                   session=lay["session"])
    return spec.Cell(name=f"small-{layout}", chips=lay["chips"],
                     config_name=config["name"], traffic_name="small",
                     config=config, traffic=traffic, end_to_end=[],
                     per_layer=[])


def run_small(layout, seed=2**31 + 7):
    import jax

    from bench import run

    return run.run_cell(small_cell(layout), seed, 0.5, False,
                        jax.devices(), peaks={}, limits=SMALL_LIMITS[layout])


def plant(fault):
    """Break the program's timed path; returns an undo."""
    import repro.models.transformer as tr
    import repro.train.overlap_grads as og
    import repro.train.train_step as ts

    if fault == "state_unchanged":
        mod, attr = ts, "make_train_step"
        orig = ts.make_train_step

        def broken(model, opt):
            good = orig(model, opt)

            def step(state, batch):
                _, metrics = good(state, batch)
                return state, metrics
            return step
    elif fault == "half_batch":
        mod, attr = tr.DecoderLM, "loss"
        orig = tr.DecoderLM.loss

        def broken(self, params, batch):
            n = batch["tokens"].shape[0] // 2
            return orig(self, params, {k: v[:n] for k, v in batch.items()})
    elif fault == "no_exchange":
        mod, attr = og, "run_overlapped"
        orig = og.run_overlapped

        def broken(x, mesh, axis, plan, compute=(), **kw):
            return x, [fn() for fn in compute]
    elif fault == "sound":
        return lambda: None
    else:
        raise ValueError(fault)
    setattr(mod, attr, broken)
    return lambda: setattr(mod, attr, orig)


@pytest.mark.parametrize("fault", ["sound", "state_unchanged", "half_batch"])
def test_one_chip_cell(fault):
    undo = plant(fault)
    try:
        out = run_small("granite")
    finally:
        undo()
    assert out["attempted"] > 0
    assert out["correct"] is (fault == "sound"), out["checked"]


CHILD = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {root!r} + "/tests/bench", {root!r} + "/src"]
    import test_bench_faults as t
    undo = t.plant({fault!r})
    out = t.run_small("qwen2")
    print(json.dumps({{"correct": out["correct"],
                      "checked": out["checked"]}}))
""")


@pytest.mark.parametrize("fault", ["sound", "no_exchange"])
def test_four_chip_cell(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", CHILD.format(root=str(ROOT), fault=fault)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is (fault == "sound"), out["checked"]


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_control_fails_the_check(layout):
    """The control -- the reference with every matrix product's operands
    rounded to float8, put in the program's place -- reads above at
    least one limit on the cell's first checked steps."""
    import jax

    from bench import data, spec
    from bench.kinds.train import compare

    c = small_cell(layout)
    ref = spec.reference(c.config["family"])
    m, t = c.config["model"], c.traffic
    rows = t["batch_per_chip"] * c.chips
    mesh = jax.make_mesh((1,), ("r",))
    for seed in (11, 12, 13):
        batches = [(b["tokens"], b["labels"]) for b in (
            data.batch(seed, i, rows, t["seq"], m["vocab_size"])
            for i in range(t["check_steps"]))]
        want = ref.train(m, t["optimizer"], mesh, seed, batches)
        got = ref.train(m, t["optimizer"], mesh, seed, batches,
                        matmul_dtype="float8_e4m3fn")
        checked = compare(got, want, SMALL_LIMITS[layout])
        assert any(x["value"] > x["limit"] for x in checked.values()), \
            checked
