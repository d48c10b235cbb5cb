"""A cell, a configuration, a traffic mix and a per-layer metric are found
by name: adding them adds files and edits none."""

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import core

from .helpers import run_on_devices

ROOT = Path(__file__).resolve().parents[2]


def test_every_cell_of_the_benchmark_loads():
    bench = core.load_benchmark()
    for w in bench["workloads"]:
        cell = core.load_cell(w["name"])
        core.load_runner(cell)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(core.load_reader(m["name"]))


def test_new_files_are_found_by_name(tmp_path):
    bench_dir = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    base = spec["workloads"][0]
    conf = next(c for c in spec["configs"] if c["name"] == base["config"])
    cfg = json.loads((ROOT / conf["file"]).read_text())
    cfg["assumed"] = cfg.get("assumed", []) + ["a copy"]
    (bench_dir / "configs" / "copy.json").write_text(json.dumps(cfg))
    traffic = json.loads((bench_dir / "traffic" / f"{base['traffic']}.json").read_text())
    traffic["note"] = "a copy"
    (bench_dir / "traffic" / "copy-mix.json").write_text(json.dumps(traffic))
    (bench_dir / "metrics" / "copy_s.new.py").write_text(
        "def read(run):\n    return run.spans.get('copy')\n")
    moves = next(m["name"] for m in spec["end_to_end"]
                 if m["name"] != "setup_s" and base["name"] in m.get("workloads", [base["name"]]))
    spec["configs"].append({**conf, "name": "copy", "file": "bench/configs/copy.json"})
    spec["workloads"].append({**base, "name": "new.copy.cell", "config": "copy",
                              "traffic": "copy-mix"})
    spec["per_layer"].append({"name": "copy_s.new", "unit": "s", "better": "lower",
                              "source": "host_clock", "layer": "copy", "moves": moves,
                              "workloads": ["new.copy.cell"]})
    for m in spec["end_to_end"]:
        if "workloads" in m and base["name"] in m["workloads"]:
            m["workloads"].append("new.copy.cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = core.load_cell("new.copy.cell", root=tmp_path, bench_dir=bench_dir)
    assert cell.config["assumed"][-1] == "a copy"
    assert cell.traffic["note"] == "a copy"
    assert cell.runner == json.loads((ROOT / conf["file"]).read_text())["runner"]
    assert core.load_runner(cell).Runner
    assert "copy_s.new" in [m["name"] for m in cell.per_layer]
    view = core.RunView(cell=cell, chips=1, peaks={}, window_s=1.0, units=2,
                        e2e={}, spans={"copy": 3.0})
    assert core.per_layer_values(view)["copy_s.new"] == {"value": 3.0, "unit": "s"}


def test_missing_pieces_are_errors(tmp_path):
    with pytest.raises(core.CellError):
        core.load_cell("no.such.cell")
    with pytest.raises(core.CellError):
        core.load_benchmark(tmp_path)
    with pytest.raises(core.CellError):
        core.load_reader("no_such_metric")


def _run(cwd, env_extra):
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train.minicpm-2b.s1024",
         "--seed", "2147483999", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_a_machine_without_tpu():
    proc = _run(ROOT, {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_refuses_a_checkout_with_only_the_benchmark(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _run(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def _cells(bench):
    return [w["name"] for w in bench["workloads"]]


def _end_to_end(bench, cell):
    return [m["name"] for m in bench["end_to_end"] if core._reports(m, cell)]


def test_every_cell_reports_set_up_and_one_other_end_to_end_metric():
    bench = core.load_benchmark()
    for cell in _cells(bench):
        names = _end_to_end(bench, cell)
        assert "setup_s" in names, cell
        assert len(names) == 2, (cell, names)


def test_every_per_layer_metric_moves_what_its_cells_report():
    bench = core.load_benchmark()
    cells = _cells(bench)
    for m in bench["per_layer"]:
        listed = m.get("workloads", cells)
        assert listed and set(listed) <= set(cells), m["name"]
        for cell in listed:
            assert m["moves"] in _end_to_end(bench, cell), (m["name"], cell)


def test_at_most_half_the_cells_ask_for_four_chips():
    bench = core.load_benchmark()
    chips = [w["chips"] for w in bench["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 2)


TRACED = """
import dataclasses, json, sys
from bench import core
from bench.tests.helpers import run_cell, small_cell

name = sys.argv[1]
listed = core.load_cell(name)
# A name of its own keeps its trace directory apart from other tests' runs.
cell = dataclasses.replace(small_cell(name), name=name + ".discovery",
                           per_layer=listed.per_layer,
                           end_to_end=listed.end_to_end)
res = run_cell(cell, 2**31 + 53, seconds=1.0, trace=1)
print(json.dumps({"correct": res["correct"], "count": res["device"]["count"],
                  "metrics": res["metrics"]}))
"""


@pytest.mark.parametrize("name", _cells(core.load_benchmark()))
def test_traced_run_reads_every_host_metric(name):
    """A small traced run of each cell on the CPU, on as many virtual
    devices as the cell asks for chips: every per-layer metric that the
    host's spans or counters give has a value.  Those read from the
    device's trace find no device there; the chip shows them."""
    listed = core.load_cell(name)
    res = run_on_devices(TRACED, name, devices=listed.chips)
    assert res["correct"] is True and res["count"] == listed.chips
    host = [m["name"] for m in listed.per_layer if m["source"] != "device_trace"]
    assert host and set(host) <= set(res["metrics"]), (host, res["metrics"])
