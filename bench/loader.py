"""Finds a cell's parts by name.

``BENCHMARK.json`` (beside ``bench/``) lists the cells; a cell names its
configuration and its traffic mix, found as ``bench/configs/<config>.json``
and ``bench/traffic/<mix>.json``. A metric applies to a cell when its
``workloads`` list names the cell or when it has no such list; a per-layer
metric's reader is ``bench/metrics/<metric>.py``, whose ``read(ctx)``
returns the number or ``None`` when there is nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from bench.traffic import Mix

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    mix: Mix
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    readers: Dict[str, Callable] = field(default_factory=dict)


def _check_name(kind: str, name: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise ValueError(f"{kind} name {name!r} is not a valid name")
    return name


def _read_json(path: Path, kind: str, name: str) -> Dict[str, Any]:
    if not path.is_file():
        raise ValueError(f"unknown {kind} {name!r}: no file "
                         f"{path.parent.name}/{path.name}")
    return json.loads(path.read_text())


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_config(name: str, bench_dir: Path = BENCH_DIR) -> Dict[str, Any]:
    _check_name("config", name)
    return _read_json(bench_dir / "configs" / f"{name}.json", "config", name)


def load_mix(name: str, bench_dir: Path = BENCH_DIR) -> Mix:
    _check_name("traffic", name)
    spec = _read_json(bench_dir / "traffic" / f"{name}.json", "traffic",
                      name)
    return Mix.from_spec(name, spec)


def load_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    _check_name("metric", name)
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"unknown metric {name!r}: no reader {path.name}")
    spec = importlib.util.spec_from_file_location(
        "bench.metrics._" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Optional[Dict[str, Any]] = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration, mix
    and metric readers. An unknown name of any part raises ValueError."""
    bench = load_benchmark(bench_dir.parent) if bench is None else bench
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise ValueError(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{[w['name'] for w in bench['workloads']]}")
    w = found[0]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    layer = [m for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=load_config(w["config"], bench_dir),
                mix=load_mix(w["traffic"], bench_dir), end_to_end=e2e,
                per_layer=layer,
                readers={m["name"]: load_reader(m["name"], bench_dir)
                         for m in layer})
