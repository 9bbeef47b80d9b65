"""Find a cell and everything it names, by name, from ``BENCHMARK.json``.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's entry gives its file; the mix is
``traffic/<traffic>.json``; each per-layer metric is read by
``metrics/<name>.py`` or, shared by the metrics of one base name, by
``metrics/<base>.py``; a configuration's plain reference is
``references/<reference>.py``.  A later cell, configuration, mix or metric
is added by adding files and entries, never by editing this code.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]       # benchmarks/chip
ROOT = BENCH_DIR.parents[1]                           # the checkout


@dataclass
class Cell:
    name: str
    chips: int
    config: dict                   # the configuration file, as run
    traffic: dict                  # the traffic mix file
    end_to_end: List[dict]         # metric entries this cell reports
    per_layer: List[dict]
    readers: Dict[str, Path] = field(default_factory=dict)
    reference: Optional[Path] = None

    @property
    def limits(self) -> dict:
        """The limits of ``correct``: the configuration's, with the mix's
        own over them (how far a sound run's flags disagree depends on
        the frames the mix keeps in the lanes)."""
        return {**self.config["limits"], **self.traffic.get("limits", {})}


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def resolve(name: str, bench: Optional[dict] = None, root: Path = ROOT,
            bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell called ``name``, with its files loaded.  Raises KeyError
    for an unknown cell and FileNotFoundError for a missing file."""
    bench = load_benchmark(root) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(bench_dir / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    readers = {m["name"]: find_reader(m["name"], bench_dir)
               for m in per_layer}
    reference = bench_dir / "references" / f"{config['reference']}.py"
    if not reference.is_file():
        raise FileNotFoundError(f"configuration {w['config']!r} names no "
                                f"reference at {reference}")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                readers=readers, reference=reference)


def find_reader(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    """``metrics/<name>.py``, else the reader of the name's base (what
    precedes its first ``.``), which the split metrics share:
    ``dispatch_ms.sat`` and ``dispatch_ms.rate`` are read by
    ``metrics/dispatch_ms.py``."""
    tried = [bench_dir / "metrics" / f"{n}.py"
             for n in dict.fromkeys((name, name.split(".")[0]))]
    for path in tried:
        if path.is_file():
            return path
    raise FileNotFoundError(f"metric {name!r} has no reader: tried "
                            f"{[str(p) for p in tried]}")


def load_module(path: Path, name: Optional[str] = None):
    """Import one file of the benchmark by its path."""
    spec = importlib.util.spec_from_file_location(
        name or f"chipbench_file_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(path: Path) -> Callable:
    """A per-layer metric's ``read(run) -> float | None``."""
    return load_module(path).read
