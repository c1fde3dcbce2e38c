"""Find a cell's parts by name and put its result line together.

A cell is ``workloads/<cell>.json``. It names its configuration
(``configs/<config>.json``), its traffic driver (``drivers/<kind>.py``) and
the end-to-end metrics it reports. Every per-layer metric is a reader of its
own, ``metrics/<metric>.py``, that declares the one end-to-end metric it
moves; a traced run reads the metrics that ``BENCHMARK.json`` lists for the
cell. Adding a cell, a driver or a metric is adding a file and entries.

This module imports no JAX, so that the tests of what it finds run without
a device.
"""

from __future__ import annotations

import importlib.util
import json
import math
import random
import re
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Any, Callable

BENCH = Path(__file__).resolve().parent
SPEC = BENCH.parent / "BENCHMARK.json"

#: The end-to-end metric that every cell reports.
SETUP = "setup_s"

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
_METRIC_KEYS = ("UNIT", "BETTER", "LAYER", "SOURCE", "MOVES", "read")


class BenchError(Exception):
    """A cell, configuration, driver, metric or peak that is not there or
    not well formed."""


def _check_name(kind: str, name: str) -> str:
    if not _NAME.match(name):
        raise BenchError(f"bad {kind} name {name!r}")
    return name


def _load_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise BenchError(f"no {what} at {path}")
    with open(path) as f:
        return json.load(f)


def _load_module(path: Path, what: str) -> ModuleType:
    if not path.is_file():
        raise BenchError(f"no {what} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{what}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Cell:
    """One workload: a configuration under one traffic mix."""

    name: str
    config_name: str
    traffic: str
    driver: str
    chips: int
    end_to_end: tuple[str, ...]
    params: dict
    why: str
    config: dict = field(repr=False)


def load_cell(name: str, root: Path = BENCH) -> Cell:
    """The cell ``workloads/<name>.json`` with its configuration."""
    _check_name("workload", name)
    w = _load_json(root / "workloads" / f"{name}.json", "workload")
    config_name = _check_name("config", w["config"])
    config = _load_json(root / "configs" / f"{config_name}.json", "config")
    chips = int(w["chips"])
    if chips not in (1, 4):
        raise BenchError(f"{name}: chips must be 1 or 4, got {chips}")
    e2e = tuple(w["end_to_end"])
    if not e2e or SETUP in e2e:
        raise BenchError(f"{name}: end_to_end lists the cell's own metrics, "
                         f"{SETUP} besides")
    return Cell(name=name, config_name=config_name,
                traffic=_check_name("traffic", w["traffic"]),
                driver=_check_name("driver", w["driver"]), chips=chips,
                end_to_end=e2e, params=dict(w["params"]), why=w["why"],
                config=config)


def cell_names(root: Path = BENCH) -> list[str]:
    return sorted(p.stem for p in (root / "workloads").glob("*.json"))


def load_driver(kind: str, root: Path = BENCH) -> ModuleType:
    """The traffic driver ``drivers/<kind>.py``."""
    mod = _load_module(root / "drivers" / f"{_check_name('driver', kind)}.py",
                       "driver")
    for attr in ("END_TO_END", "setup"):
        if not hasattr(mod, attr):
            raise BenchError(f"driver {kind} has no {attr}")
    return mod


@dataclass(frozen=True)
class Metric:
    """A per-layer metric: its declarations and its reader."""

    name: str
    unit: str
    better: str
    layer: str
    source: str
    moves: str
    read: Callable[[Any], float | None]


def load_metrics(root: Path = BENCH) -> dict[str, Metric]:
    """Every reader under ``metrics/``, by name."""
    out = {}
    for path in sorted((root / "metrics").glob("*.py")):
        name = _check_name("metric", path.stem)
        mod = _load_module(path, "metric")
        missing = [k for k in _METRIC_KEYS if not hasattr(mod, k)]
        if missing:
            raise BenchError(f"metric {name} lacks {missing}")
        out[name] = Metric(name, mod.UNIT, mod.BETTER, mod.LAYER,
                           mod.SOURCE, mod.MOVES, mod.read)
    return out


def load_spec(path: Path = SPEC) -> dict:
    """``BENCHMARK.json``: the cells and the metrics each reports."""
    return _load_json(path, "benchmark spec")


def metrics_for(cell: Cell, metrics: dict[str, Metric],
                spec: dict) -> list[Metric]:
    """The per-layer metrics a traced run of ``cell`` reads: those whose
    entry in ``spec`` lists the cell under ``workloads``."""
    out = []
    for entry in spec["per_layer"]:
        if cell.name in entry["workloads"]:
            try:
                out.append(metrics[entry["name"]])
            except KeyError:
                raise BenchError(f"no reader for per-layer metric "
                                 f"{entry['name']!r}") from None
    return out


def load_peaks(device_kind: str, root: Path = BENCH) -> dict:
    """The published peaks of one device kind; an unknown kind is an
    error, never a default."""
    table = _load_json(root / "peaks.json", "peak table")
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json") from None


class Reservoir:
    """A uniform sample of at most ``k`` of the answers offered, drawn from
    the seed (Vitter's algorithm R): which answers are checked is fixed by
    the seed and the count, whatever the timing."""

    def __init__(self, k: int, seed: int):
        if k < 1:
            raise BenchError(f"sample size must be >= 1, got {k}")
        self.k = k
        self._rng = random.Random(seed)
        self._seen = 0
        self.items: list[tuple[int, Any]] = []

    def offer(self, item: Any) -> None:
        i = self._seen
        self._seen += 1
        if len(self.items) < self.k:
            self.items.append((i, item))
        else:
            j = self._rng.randrange(i + 1)
            if j < self.k:
                self.items[j] = (i, item)


@dataclass
class Check:
    """One number compared with its limit: ``ok`` when ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def result_line(*, checks: list[Check], attempted: int, failed: int,
                metrics: dict[str, tuple[float, str]], device: dict,
                breakdown: dict | None = None,
                compiles_in_window: int = 0) -> str:
    """The last line of standard output: one JSON object, the numbers
    compared last."""
    out: dict[str, Any] = {
        "correct": bool(checks) and all(c.ok for c in checks),
        "attempted": attempted,
        "failed": failed,
        "metrics": {k: {"value": v, "unit": u}
                    for k, (v, u) in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compiles_in_window"] = compiles_in_window
    out["checks"] = {c.name: {"value": _finite(c.value), "limit": c.limit}
                     for c in checks}
    return json.dumps(out, allow_nan=False)


def _finite(v: float) -> float | str:
    """A number for JSON: one that is not finite is written as text."""
    return v if math.isfinite(v) else str(v)
