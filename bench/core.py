"""What the harness reads from files: the cell, its configuration and traffic,
the per-layer metric readers and the table of peaks.

Everything here is found by name.  A workload of ``BENCHMARK.json`` names a
configuration and a traffic mix; the configuration's file names the runner
(``bench/runners/<runner>.py``) that runs it; the traffic mix is
``bench/traffic/<traffic>.json``; a per-layer metric is read by
``bench/metrics/<metric name>.py``.  Adding a cell, a configuration, a
traffic mix or a metric adds files and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class CellError(RuntimeError):
    """The benchmark's files do not describe the requested cell."""


@dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it refers to."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]
    bench_dir: Path = field(default=BENCH_DIR)

    @property
    def runner(self) -> str:
        return self.config["runner"]


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise CellError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT, bench_dir: Path = BENCH_DIR) -> Cell:
    bench = load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise CellError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {name!r} names unknown config {w['config']!r}")
    cfg_path = root / configs[w["config"]]["file"]
    traffic_path = bench_dir / "traffic" / f"{w['traffic']}.json"
    for p in (cfg_path, traffic_path):
        if not p.is_file():
            raise CellError(f"missing {p}")
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, name))
    e2e_names = {m["name"] for m in e2e}
    per_layer = tuple(
        m for m in bench["per_layer"]
        if _reports(m, name) and m["moves"] in e2e_names
    )
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=w["config"],
        config=json.loads(cfg_path.read_text()),
        traffic_name=w["traffic"],
        traffic=json.loads(traffic_path.read_text()),
        end_to_end=e2e,
        per_layer=per_layer,
        bench_dir=bench_dir,
    )


def _load_file(path: Path, modname: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise CellError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_runner(cell: Cell) -> ModuleType:
    path = cell.bench_dir / "runners" / f"{cell.runner}.py"
    if not path.is_file():
        raise CellError(f"config {cell.config_name!r} names missing runner {path}")
    return _load_file(path, f"bench_runner_{cell.runner}")


def load_reader(metric: str, bench_dir: Path = BENCH_DIR):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = bench_dir / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise CellError(f"per-layer metric {metric!r} has no reader {path}")
    mod = _load_file(path, "bench_metric_" + metric.replace(".", "_"))
    return mod.read


def load_peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """Published peaks of one chip; a device missing from the table is an
    error, never a default."""
    table = json.loads((bench_dir / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise CellError(
            f"no peaks for device kind {device_kind!r} in peaks.json"
        ) from None


@dataclass
class Compared:
    """One number the correctness check compares, beside its limit.
    ``at_least`` marks a lower limit; otherwise the number must not exceed
    the limit."""

    name: str
    value: float
    limit: float
    at_least: bool = False

    @property
    def ok(self) -> bool:
        if self.value != self.value:  # NaN never passes
            return False
        return self.value >= self.limit if self.at_least else self.value <= self.limit

    def line(self) -> str:
        op = ">=" if self.at_least else "<="
        return f"{self.name} {self.value!r} {op} {self.limit!r} {'ok' if self.ok else 'FAIL'}"

    def as_json(self) -> dict:
        return {"value": self.value, "limit": self.limit,
                "at_least": self.at_least, "ok": self.ok}


@dataclass
class Window:
    """What a runner's measured window produced."""

    elapsed: float  # seconds, host clock, first unit's start to last's end
    units: int  # decisions or steps completed
    unit_name: str
    e2e: dict  # end-to-end metric name -> value
    traced_units: int = 0
    extra: dict = field(default_factory=dict)


@dataclass
class RunView:
    """What a per-layer metric reader sees of one traced run."""

    cell: Cell
    chips: int
    peaks: dict
    window_s: float  # the whole measured window
    units: int  # decisions or steps in the whole window
    e2e: dict  # end-to-end values measured in this run
    counters: dict = field(default_factory=dict)
    spans: dict = field(default_factory=dict)  # name -> host seconds in window
    trace: object = None  # trace.TraceSummary of the traced part, or None
    traced_units: int = 0  # decisions or steps inside the traced part
    extra: dict = field(default_factory=dict)  # runner facts (flops/token)


def per_layer_values(view: RunView) -> dict:
    """Run each of the cell's readers; a reader that finds nothing returns
    None and its metric is left out."""
    out = {}
    for m in view.cell.per_layer:
        value = load_reader(m["name"], view.cell.bench_dir)(view)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
