"""``BENCHMARK.json`` and the files it names: every configuration,
traffic mix, metric reader and family module loads by name, names and
units use only the allowed characters, the peaks table refuses a device
it does not list, and the command refuses to run without a TPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1] in ("bench/run.py",)
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in METRICS]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    for n in names:
        assert spec.NAME_RE.match(n), n
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        assert len({x["name"] for x in group}) == len(group)
    for m in METRICS:
        assert spec.UNIT_RE.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load_by_name(cell):
    c = spec.cell(cell, BENCH)
    fam = c.config["family"]
    assert c.config["name"] == c.config_name
    assert spec.flops(fam).train_flops_per_token(c.config["model"],
                                                 c.traffic["seq"]) > 0
    assert hasattr(spec.reference(fam), "train")
    assert hasattr(spec.kind(c.traffic["kind"]), "run")
    assert set(spec.limits(cell)) >= {"loss_gap", "grad_gap", "change_gap"}
    row = [x for x in BENCH["configs"] if x["name"] == c.config_name][0]
    assert (ROOT / row["file"]).is_file()
    assert sorted(row["reduced"]) == sorted(c.config["reduced"])
    # every cell reports setup_s, another end-to-end metric and a
    # per-layer one
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_readers_load_by_name(metric):
    m = [x for x in BENCH["per_layer"] if x["name"] == metric][0]
    assert callable(spec.metric_reader(metric).read)
    assert m["moves"] in {x["name"] for x in BENCH["end_to_end"]}
    assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_missing_name_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.cell("no-such-cell", BENCH)
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no.such.metric")


def test_peaks_by_device_kind():
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(spec.SpecError, match="not in"):
        spec.peaks("cpu")


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
