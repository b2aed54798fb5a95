"""Find the benchmark's pieces by name.

``BENCHMARK.json`` names the cells, configurations, traffic mixes and
metrics.  Each has a file of its own under ``bench/``:

* configuration ``<c>``: ``bench/configs/<c>.json``;
* traffic ``<t>``: ``bench/traffic/<t>.json``;
* per-layer metric ``<m>``: ``bench/metrics/<m>.py`` with ``read(ctx)``;
* model family ``<f>``: ``bench/flops/<f>.py`` and
  ``bench/reference/<f>.py``;
* traffic kind ``<k>``: ``bench/kinds/<k>.py`` (set-up, window, check);
* cell ``<w>``: the limits of its output check, ``bench/limits/<w>.json``;
* device kind: a row of ``bench/peaks.json``.

Adding one of them is adding files and entries; no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class SpecError(ValueError):
    """A name in ``BENCHMARK.json`` has no file, or a file is malformed."""


def _read_json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise SpecError(f"{path.relative_to(ROOT)} does not exist")
    return json.loads(path.read_text())


def _module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise SpecError(f"{path.relative_to(ROOT)} does not exist")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> Dict[str, Any]:
    return _read_json(ROOT / "BENCHMARK.json")


def config(name: str) -> Dict[str, Any]:
    return _read_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> Dict[str, Any]:
    return _read_json(BENCH / "traffic" / f"{name}.json")


def limits(cell: str) -> Dict[str, float]:
    return _read_json(BENCH / "limits" / f"{cell}.json")


def metric_reader(name: str) -> ModuleType:
    return _module(BENCH / "metrics" / f"{name}.py", f"bench_metric_{name}")


def flops(family: str) -> ModuleType:
    return _module(BENCH / "flops" / f"{family}.py", f"bench_flops_{family}")


def reference(family: str) -> ModuleType:
    return _module(BENCH / "reference" / f"{family}.py",
                   f"bench_reference_{family}")


def kind(name: str) -> ModuleType:
    return _module(BENCH / "kinds" / f"{name}.py", f"bench_kind_{name}")


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``.

    A device missing from the table is an error, never a default."""
    table = _read_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"bench/peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: Optional[Dict[str, Any]] = None) -> Cell:
    bench = bench or benchmark()
    rows = [w for w in bench["workloads"] if w["name"] == name]
    if not rows:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"({[w['name'] for w in bench['workloads']]})")
    w = rows[0]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], config=config(w["config"]),
        traffic=traffic(w["traffic"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])
