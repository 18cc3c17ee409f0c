"""Find the benchmark's pieces by name: one file per configuration, cell,
driver and per-layer metric, so that a new one is a new file and no edit."""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Cell:
    """One workload file joined with the configuration file it names."""
    name: str
    chips: int
    driver: str
    traffic: Dict[str, Any]
    config: Dict[str, Any]


def _load_json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    with open(path) as f:
        return json.load(f)


def workload_names(root: Path = ROOT) -> List[str]:
    return sorted(p.stem for p in (root / "workloads").glob("*.json"))


def load_cell(name: str, root: Path = ROOT) -> Cell:
    wl = _load_json(root / "workloads" / f"{name}.json")
    if wl.get("name") != name:
        raise ValueError(f"workload file {name}.json names {wl.get('name')!r}")
    cfg = _load_json(root / "configs" / f"{wl['config']}.json")
    return Cell(name=name, chips=int(wl["chips"]), driver=wl["driver"],
                traffic=dict(wl["traffic"]), config=cfg)


def _load_module(path: Path, modname: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_driver(kind: str, root: Path = ROOT) -> ModuleType:
    return _load_module(root / "drivers" / f"{kind}.py", f"chipbench_driver_{kind}")


def layer_metric_readers(kind: str, root: Path = ROOT) -> Dict[str, ModuleType]:
    """Every reader in ``layer_metrics/`` that reads runs of driver ``kind``,
    by metric name (the file's name without ``.py``)."""
    out = {}
    for path in sorted((root / "layer_metrics").glob("*.py")):
        name = path.name[:-3]
        mod = _load_module(path, "chipbench_metric_" + name.replace(".", "_"))
        if mod.KIND == kind:
            out[name] = mod
    return out


def peaks(device_kind: str, root: Path = ROOT) -> Dict[str, float]:
    """The chip's published peaks; a kind not in the table is an error."""
    table = _load_json(root / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table["devices"][device_kind]
