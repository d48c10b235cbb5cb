"""The per-layer metrics read from the program's own spans
(``repro.telemetry``): whole traced runs of the training cell, a window
step that builds an executable, a program without the recorder, and idle
gaps still named by the benchmark's spans alone."""

import dataclasses
import sys

import pytest

from bench import core, tracefile

from .helpers import run_cell, small_cell
from .test_tracefile import device, ev, host

CELL = "train.minicpm-2b.s1024"
READ = ("dispatch_ms.train", "prefetch_wait_ms.train", "compiles.train")


def _cell():
    """The small training cell with the per-layer metrics the benchmark
    gives the full one."""
    listed = core.load_cell(CELL)
    return dataclasses.replace(small_cell(CELL), per_layer=listed.per_layer,
                               end_to_end=listed.end_to_end)


def test_traced_run_reads_the_program_spans():
    res = run_cell(_cell(), 2**31 + 43, trace=1)
    m = res["metrics"]
    assert set(READ) <= set(m)
    assert m["compiles.train"] == {"value": 0.0, "unit": "executables"}
    assert m["dispatch_ms.train"]["value"] > 0.0
    # The program's wait lies inside the harness's span round the same call.
    assert 0.0 < m["prefetch_wait_ms.train"]["value"] <= m["data_wait_ms.train"]["value"]
    assert res["correct"] is True


def test_a_window_step_of_another_length_reads_a_compile(monkeypatch):
    import repro.data.pipeline as pipeline

    cell = _cell()
    at = cell.traffic["check_steps"] + 1  # the window's second step
    real = pipeline.batch_for_step

    def batch(spec, step):
        b = real(spec, step)
        return {"tokens": b["tokens"][:, :64]} if step == at else b

    monkeypatch.setattr(pipeline, "batch_for_step", batch)
    res = run_cell(cell, 2**31 + 47, trace=1)
    assert res["metrics"]["compiles.train"]["value"] >= 1


def _view(units):
    return core.RunView(cell=_cell(), chips=1, peaks={}, window_s=1.0,
                        units=units, e2e={})


@pytest.mark.parametrize("metric", READ)
def test_nothing_to_read_gives_none(monkeypatch, metric):
    import repro
    from repro import telemetry

    with telemetry.span("train.dispatch"), telemetry.span("data.wait"):
        pass
    read = core.load_reader(metric)
    assert read(_view(10**6)) is None  # fewer records than window steps
    assert read(_view(0)) is None
    # A program without the recorder, as before it had one.
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    monkeypatch.delattr(repro, "telemetry")
    assert read(_view(1)) is None


def test_program_spans_do_not_name_idle_gaps():
    """Only ``bench:`` spans name a gap; a program span inside one leaves
    the gap the harness span's, and alone leaves it ``host``."""
    planes = [
        host([ev("bench:window", 0, 1000), ev("bench:step", 100, 300),
              ev("repro/train.dispatch", 150, 100),
              ev("repro/data.wait", 600, 100)]),
        device(0, [ev("op", 0, 150), ev("op", 250, 350), ev("op", 700, 300)]),
    ]
    gaps = tracefile.summarize(planes, chips=1).idle_gaps
    assert sorted((name, round(s * 1e9)) for name, s in gaps) == [
        ("host", 100), ("step", 100)]
