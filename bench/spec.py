"""Finding the benchmark's data by name: ``BENCHMARK.json`` and the files
under ``bench/`` that a later change adds without editing any other.

- ``bench/configs/<config>.json``: a model configuration as run;
- ``bench/traffic/<traffic>.json``: a traffic mix (``bench/generator.py``);
- ``bench/rates/<cell>.json``: ``{"rate_rps": ...}`` of an open-loop cell;
- ``bench/limits/<config>.json``: the limits ``correct`` holds the
  configuration's outputs to;
- ``bench/metrics/<metric>.py``: the reader of one per-layer metric, a
  function ``read(window)`` returning a number or None.  A metric that
  reads the same in other cells, under a name of its own so that it can
  move another end-to-end metric (``<base>.<tag>``), has no file of its
  own and is read by ``<base>.py``;
- ``bench/families/<family>.py``: what the harness knows of one kind of
  model (the program's config, block columns, FLOPs, the reference), named
  by a configuration's ``bench_family`` key, ``gqa`` where it has none;
- ``bench/peaks.json``: the chip's peaks, keyed by ``device_kind``.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict:
    return _json(ROOT / "BENCHMARK.json")


def cell(name: str) -> Dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> Dict:
    return _json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> Dict:
    return _json(BENCH / "traffic" / f"{name}.json")


def rate(cell_name: str) -> Optional[float]:
    path = BENCH / "rates" / f"{cell_name}.json"
    return float(_json(path)["rate_rps"]) if path.exists() else None


def limits(config_name: str) -> Dict:
    return _json(BENCH / "limits" / f"{config_name}.json")


def peaks(device_kind: str) -> Dict:
    table = _json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str) -> Callable:
    path = BENCH / "metrics" / f"{metric}.py"
    base = BENCH / "metrics" / f"{metric.rsplit('.', 1)[0]}.py"
    if not path.exists() and base.exists():
        path = base
    return _load(path, f"bench_metric_{metric}").read


def family(cfg: Dict):
    """The family module of a configuration file's dict.  Loaded once per
    file, so that what it compiles is compiled once per process."""
    path = BENCH / "families" / f"{cfg.get('bench_family', 'gqa')}.py"
    name = f"bench_family_{path.stem}"
    mod = sys.modules.get(name)
    if mod is None or mod.__file__ != str(path):
        mod = sys.modules[name] = _load(path, name)
    return mod


def metrics_of(cell_name: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [m for m in benchmark()[kind] if "workloads" not in m or cell_name in m["workloads"]]
