import json

import pytest

from bench import core
from bench.runners import train
from bench.ref import minicpm


def test_v5e_peaks():
    p = core.load_peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


def test_unknown_device_is_an_error():
    with pytest.raises(core.CellError):
        core.load_peaks("TPU v99")


def test_minicpm_model_flops():
    cfg = json.loads((core.BENCH_DIR / "configs" / "minicpm-2b.json").read_text())
    dm = minicpm.dims(cfg)
    per_token = train.model_flops_per_token(dm, 1024)
    d, f, v = 2304, 5760, 122753
    weights = 10 * (4 * d * d + 3 * d * f) + d * v
    assert weights == pytest.approx(892.7e6, rel=1e-3)
    assert per_token == pytest.approx(6 * weights + 10 * 6 * 1024 * 2304)
    assert 5.4e9 < per_token < 5.6e9


def test_model_flops_match_the_program_parameter_count():
    """Every weight the model multiplies by is counted once: the program's
    parameters less the norm gains and with the tied embedding once."""
    import jax

    from repro.models import lm

    cfg = json.loads((core.BENCH_DIR / "configs" / "minicpm-2b.json").read_text())
    prog = train.program_config(cfg)
    specs = lm.param_specs(prog)
    n = sum(x.size for path, x in jax.tree_util.tree_flatten_with_path(specs)[0]
            if "norm" not in jax.tree_util.keystr(path))
    dm = minicpm.dims(cfg)
    per_token = train.model_flops_per_token(dm, 1)
    assert per_token - 6 * dm["layers"] * dm["heads"] * dm["hd"] == 6.0 * n


class _SlowTracer:
    """A profiler stand-in whose stop takes as long as writing a large
    trace does."""

    def __init__(self, stop_s):
        self.stop_s = stop_s
        self.started = self.stopped = False

    def start(self):
        self.started = True

    def stop(self):
        import time

        if self.started and not self.stopped:
            time.sleep(self.stop_s)
            self.stopped = True


def test_step_mfu_leaves_out_the_profiler():
    """A traced run's ``step_mfu`` takes its rate from the steps after the
    profiler has stopped: the time it takes to stop is not in it."""
    from bench.spans import Spans
    from bench.tests.helpers import small_cell

    cell = small_cell("train.minicpm-2b.s1024")
    runner = core.load_runner(cell).Runner(cell, __import__("jax").devices()[:1],
                                           2**31 + 41, Spans(), print)
    runner.setup()
    win = runner.window(3.0, _SlowTracer(1.0))
    runner.release()
    free = win.extra["untraced_tokens_per_s"]
    tokens = win.units * cell.traffic["seq_len"] * cell.traffic["batch"]
    # At most the time not spent stopping the profiler holds the tokens.
    assert free > tokens / (win.elapsed - 1.0) * 0.8
    assert free > 1.3 * win.e2e["tokens_per_s"]
    view = core.RunView(cell=cell, chips=1, peaks={"bf16_flops": 1e12},
                        window_s=win.elapsed, units=win.units, e2e=win.e2e,
                        extra=win.extra)
    mfu = core.load_reader("step_mfu")(view)
    assert mfu == pytest.approx(100.0 * win.extra["flops_per_token"] * free / 1e12)
