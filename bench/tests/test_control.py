"""The control, the reference in the precision below the one the
configuration states put in the program's place, comes out not correct
against the cells' limits.  On the chip the same readings come from
``bench/calibrate.py`` at the cells' own sizes."""

import ml_dtypes
import numpy as np

from bench import core

from .helpers import run_on_devices, small_cell


def test_admit_control_fails(monkeypatch):
    cell = small_cell("admit.shared432.churn")
    drv_mod = core.load_runner(cell)
    from bench.spans import Spans, Tracer

    import jax

    d = drv_mod.Runner(cell, jax.devices()[:1], 2**31 + 17, Spans(), print)
    d.setup()
    d.window(0.1, Tracer(False, ""))
    d.release()
    program = d.readings()
    control = d.readings(ml_dtypes.bfloat16)
    single = d.readings(np.float32)
    assert program["energy_gap"] <= drv_mod.ENERGY_GAP_LIMIT
    assert control["energy_gap"] > drv_mod.ENERGY_GAP_LIMIT
    assert control["price_gap"] > drv_mod.PRICE_GAP_LIMIT
    # The precision the decision needs passes: float32 pricing.
    assert single["energy_gap"] <= drv_mod.ENERGY_GAP_LIMIT


def test_train_control_reads_above_the_program():
    """At this size the int8 control reads above the program on the
    numbers it fails at the cell's size (PERF.md gives those readings)."""
    cell = small_cell("train.minicpm-2b.s1024")
    drv_mod = core.load_runner(cell)
    from bench.spans import Spans, Tracer

    import jax

    d = drv_mod.Runner(cell, jax.devices()[:1], 2**31 + 19, Spans(), print)
    d.setup()
    d.window(0.1, Tracer(False, ""))
    d.release()
    cal = d.calibration()
    assert all(c.ok for c in d.compare(cal["program"]))
    assert cal["control"]["grad_gap"] > 2 * cal["program"]["grad_gap"]
    assert cal["control"]["loss_gap"] > 2 * cal["program"]["loss_gap"]
    assert not all(c.ok for c in d.compare(cal["half_batch"]))


SYNC = """
import json
import jax
from bench import core
from bench.spans import Spans, Tracer
from bench.tests.helpers import small_cell

cell = small_cell("sync.minicpm-2b-dp4.ring")
mod = core.load_runner(cell)
d = mod.Runner(cell, jax.devices()[:4], 2**31 + 19, Spans(), lambda msg: None)
d.setup()
d.window(0.1, Tracer(False, ""))
d.release()
cal = d.calibration()
print(json.dumps({name: {c.name: [c.value, c.ok] for c in d.compare(r)}
                  for name, r in cal.items()}))
"""


def test_sync_control_reads_above_the_program():
    """The four-chip cell on four virtual devices: the int8 control reads
    above the program on the numbers it fails at the cell's size, and the
    faults the runner plants are not correct."""
    cal = run_on_devices(SYNC, devices=4)
    program, control = cal["program"], cal["control"]
    assert all(ok for _, ok in program.values())
    for name in ("loss_gap", "grad_gap"):
        assert control[name][0] > 2 * program[name][0], (name, cal)
    for fault in ("half_batch", "no_exchange"):
        assert not all(ok for _, ok in cal[fault].values()), (fault, cal)
