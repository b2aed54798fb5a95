"""Tests for the unified ``python -m repro`` CLI (ISSUE-3).

Each subcommand smoke-runs on a synthetic fabric, ``repro plan``
reproduces the manual PlanningService pipeline exactly (acceptance
criterion), the resolved config round-trips through --dump-config, the
new session/cli modules leak no DeprecationWarning, and the old entry
points survive as importable, delegating shims.
"""

import json
import subprocess
import sys
import warnings

import pytest

from repro.cli import main, session_config_from_args

PLAN_ARGS = ["--fabric", "datacenter", "--nodes", "12",
             "--scramble-seed", "1", "--iters", "80", "--chains", "2",
             "--payload-bytes", "1e6"]


def run_cli(argv):
    with warnings.catch_warnings():
        # the acceptance bar: the new CLI paths never route through the
        # deprecated shims, so repro-originated DeprecationWarnings are
        # hard errors here
        warnings.filterwarnings(
            "error", category=DeprecationWarning, module=r"repro\..*")
        return main(argv)


# ---------------------------------------------------------------------------
# subcommand smoke runs
# ---------------------------------------------------------------------------

def test_probe_smoke(tmp_path, capsys):
    out = tmp_path / "probe.json"
    assert run_cli(["probe", *PLAN_ARGS, "--out", str(out)]) == 0
    assert "[probe]" in capsys.readouterr().out
    payload = json.loads(out.read_text())
    assert payload["n"] == 12
    assert len(payload["lat"]) == 12


def test_plan_dry_run_smoke(tmp_path, capsys):
    cache = tmp_path / "plans"
    out = tmp_path / "report.json"
    assert run_cli(["plan", *PLAN_ARGS, "--dry-run", "--out", str(out),
                    "--plan-cache-dir", str(cache)]) == 0
    text = capsys.readouterr().out
    assert "[plan] dry-run:" in text
    assert "all-reduce" in text
    assert not cache.exists() or not list(cache.iterdir()), \
        "--dry-run must not write the plan store"
    assert out.exists(), "an explicit --out is written even under --dry-run"


def test_plan_writes_plan_json(tmp_path, capsys):
    from repro.plan import Plan

    out = tmp_path / "plan.json"
    assert run_cli(["plan", *PLAN_ARGS, "--mesh", "3x4",
                    "--out", str(out)]) == 0
    plan = Plan.from_json(out.read_text())
    assert plan.n == 12
    assert plan.mesh_plan is not None


def test_bench_smoke(tmp_path, capsys):
    out = tmp_path / "bench.json"
    assert run_cli(["bench", "--smoke", "--iters", "60",
                    "--out", str(out)]) == 0
    payload = json.loads(out.read_text())
    assert payload["results"][0]["cache_hits"] >= 1
    assert payload["results"][0]["warm_speedup_x"] > 1


def test_dump_config_round_trips(tmp_path, capsys):
    assert run_cli(["plan", *PLAN_ARGS, "--mesh", "3x4",
                    "--dump-config"]) == 0
    dumped = capsys.readouterr().out
    from repro.session import SessionConfig

    cfg = SessionConfig.from_json(dumped)
    assert cfg.fabric.nodes == 12
    assert cfg.mesh.shape == (3, 4)
    # feeding the dump back through --config resolves identically
    path = tmp_path / "cfg.json"
    path.write_text(dumped)
    assert run_cli(["plan", "--config", str(path), "--dump-config"]) == 0
    assert SessionConfig.from_json(capsys.readouterr().out) == cfg


# ---------------------------------------------------------------------------
# acceptance: CLI plan == manual PlanningService pipeline
# ---------------------------------------------------------------------------

def test_cli_plan_matches_manual_pipeline(tmp_path):
    """`python -m repro plan` and the hand-wired pipeline must agree on
    fingerprint key and the chosen (algo, chunks, perm) per entry."""
    from repro.core import make_datacenter, probe_fabric, scramble
    from repro.plan import Plan, PlanCache, PlanCompiler, PlanningService
    from repro.session import SessionConfig, train_mix

    out = tmp_path / "plan.json"
    assert run_cli(["plan", *PLAN_ARGS, "--out", str(out)]) == 0
    via_cli = Plan.from_json(out.read_text())

    cfg = SessionConfig()                         # the CLI's defaults
    fabric, _ = scramble(make_datacenter(12, seed=0), seed=1)
    probed = probe_fabric(fabric, seed=0)
    budget = cfg.solver.budget.__class__(iters=80, chains=2)
    service = PlanningService(
        PlanCompiler(fabric=fabric, budget=budget, seed=0), PlanCache())
    manual = service.request(probed, train_mix(1e6))
    service.close()

    assert via_cli.fingerprint.digest == manual.fingerprint.digest
    assert via_cli.mix_key == manual.mix_key
    assert set(via_cli.entries) == set(manual.entries)
    for key, e in manual.entries.items():
        ce = via_cli.entries[key]
        assert (ce.algo, ce.chunks, tuple(ce.perm)) == \
            (e.algo, e.chunks, tuple(e.perm))


def test_config_precedence_file_env_flags(tmp_path, monkeypatch):
    from repro.session import SessionConfig

    path = tmp_path / "base.json"
    SessionConfig.from_dict({"fabric": {"nodes": 20},
                             "payload_bytes": 1e5}).dump(str(path))
    monkeypatch.setenv("REPRO_PAYLOAD_BYTES", "2e5")
    ap = __import__("repro.cli", fromlist=["build_parser"]).build_parser()
    args = ap.parse_args(["plan", "--config", str(path)])
    cfg = session_config_from_args(args)
    assert cfg.fabric.nodes == 20                 # from file
    assert cfg.payload_bytes == 2e5               # env beats file
    args = ap.parse_args(["plan", "--config", str(path),
                          "--payload-bytes", "3e5"])
    cfg = session_config_from_args(args)
    assert cfg.payload_bytes == 3e5               # flag beats env


# ---------------------------------------------------------------------------
# launcher subcommands (jax): tiny smoke runs
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_train_subcommand_smoke(tmp_path, capsys):
    assert run_cli(["train", "--smoke", "--steps", "2", "--batch", "2",
                    "--seq", "16", "--ckpt-dir", str(tmp_path / "ckpt")]) == 0
    assert "[train]" in capsys.readouterr().out


@pytest.mark.slow
def test_serve_subcommand_smoke(capsys):
    assert run_cli(["serve", "--smoke", "--max-new", "2", "--batch", "2",
                    "--prompt-len", "4"]) == 0
    out = capsys.readouterr().out
    assert "[serve]" in out and "4 tokens" in out


@pytest.mark.parametrize("cmd", ["train", "serve"])
def test_launchers_default_to_published_widths(cmd):
    from repro.cli import build_parser, model_config

    ap = build_parser()
    full = model_config(ap.parse_args([cmd, "--arch", "qwen2-0.5b"]))
    assert (full.d_model, full.n_layers, full.vocab_size) == \
        (896, 24, 151936)
    smoke = model_config(ap.parse_args([cmd, "--arch", "qwen2-0.5b",
                                        "--smoke"]))
    assert smoke.d_model == 64


@pytest.mark.parametrize("cmd", ["train", "serve"])
def test_mismatched_mesh_exits_nonzero(cmd, capsys):
    # this process has one device; a 2x2 mesh cannot be built on it
    assert run_cli([cmd, "--smoke", "--mesh", "2x2"]) == 2
    err = capsys.readouterr().err
    assert f"repro {cmd}: error: --mesh 2x2 spans 4 devices but this " \
           f"process has 1" in err


def test_compile_cache_defaults_to_checkout(monkeypatch):
    import os

    import jax

    from repro.launch.compile_cache import CACHE_ENV, enable_compile_cache

    monkeypatch.delenv(CACHE_ENV, raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = enable_compile_cache()
        assert got == jax.config.jax_compilation_cache_dir
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        assert got == os.path.join(checkout, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_dir_stands(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the launcher sets no other
    directory and compiled programs land in the one JAX was given."""
    import os

    prog = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64))).block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(tmp_path)
    assert list(tmp_path.iterdir()), "nothing was cached"


def test_train_uses_every_device_with_and_without_overlap(tmp_path):
    """On a 4-device host mesh `repro train --mesh 4` shards the batch and
    places the state on all four devices, through the XLA all-reduce
    (overlap off) and the plan's certified bucketed reducer."""
    import os

    base = dict(os.environ, JAX_PLATFORMS="cpu",
                JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                XLA_FLAGS="--xla_force_host_platform_device_count=4")
    for mode in ("off", "bucketed"):
        r = subprocess.run(
            [sys.executable, "-m", "repro", "train", "--smoke", "--mesh",
             "4", "--steps", "2", "--batch", "4", "--seq", "16"],
            capture_output=True, text=True,
            env=dict(base, REPRO_OVERLAP_MODE=mode))
        assert r.returncode == 0, r.stdout + r.stderr
        overlap = "" if mode == "off" else " overlap=bucketed"
        assert f"[train] mesh {{'data': 4}}{overlap}: params on 4 " \
               f"device(s), batch on 4" in r.stdout, r.stdout


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------

def test_old_entry_points_importable_and_delegating():
    import repro.launch.serve as old_serve
    import repro.launch.train as old_train

    assert callable(old_train.main) and callable(old_serve.main)
    assert callable(old_train.build_mesh)
    with pytest.warns(DeprecationWarning, match="train_mix"):
        mix = old_train.default_job_mix(4e6, moe=True)
    assert {r.op for r in mix.requests} == {
        "all-reduce", "all-gather", "reduce-scatter", "all-to-all"}
    with pytest.warns(DeprecationWarning, match="serve_mix"):
        mix = old_serve.serve_job_mix(1e6)
    assert mix.name == "serve"


def test_module_main_entrypoint():
    """``python -m repro`` resolves (the single CLI entry point)."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro", "--version"],
        capture_output=True, text=True)
    assert proc.returncode == 0
    assert "repro" in proc.stdout


def test_lazy_top_level_exports():
    import repro

    assert repro.__version__
    assert repro.Session.__name__ == "Session"
    assert repro.JobMix.__name__ == "JobMix"
    assert repro.Fabric.__name__ == "Fabric"
    with pytest.raises(AttributeError, match="no attribute"):
        repro.not_a_thing
