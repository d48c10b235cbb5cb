"""Small stand-ins of the benchmark's cells that run on the CPU."""

import argparse
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path
from unittest import mock

from bench import core

ROOT = Path(__file__).resolve().parents[2]

# The smallest model at which bfloat16 rounding reads well under the
# limits set at the cells' own sizes (a width of 64 does not).
SMALL_MODEL = dict(num_hidden_layers=2, hidden_size=256, num_attention_heads=4,
                   num_key_value_heads=4, head_dim=64, intermediate_size=640,
                   vocab_size=8192)
TINY_MODEL = dict(SMALL_MODEL, hidden_size=64, head_dim=16,
                  intermediate_size=128, vocab_size=256)
SMALL_CLUSTER = dict(servers=64, job_size=8, resident=3)


def cell_from_files(name: str, config: str, traffic: str, chips: int) -> core.Cell:
    """A cell made from a configuration and a traffic file by name, listed
    in BENCHMARK.json or not."""
    return core.Cell(
        name=name, chips=chips, config_name=config,
        config=json.loads((core.BENCH_DIR / "configs" / f"{config}.json").read_text()),
        traffic_name=traffic,
        traffic=json.loads((core.BENCH_DIR / "traffic" / f"{traffic}.json").read_text()),
        end_to_end=(), per_layer=())


# The cells the tests drive, by the files they are made of: the same cells
# whether or not BENCHMARK.json lists them yet.
CELLS = {
    "admit.shared432.churn": ("shared432", "churn", 1),
    "train.minicpm-2b.s1024": ("minicpm-2b", "s1024", 1),
    "sync.minicpm-2b-dp4.ring": ("minicpm-2b-dp4", "ring", 4),
}


def small_cell(name: str) -> core.Cell:
    """One of :data:`CELLS` cut to a size the CPU runs in seconds."""
    cell = cell_from_files(name, *CELLS[name])
    cfg, traffic = dict(cell.config), dict(cell.traffic)
    if cell.runner in ("train", "sync"):
        cfg.update(SMALL_MODEL)
        traffic.update(seq_len=128, trace_seconds=0.2)
    else:
        cfg.update(SMALL_CLUSTER)
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def run_cell(cell: core.Cell, seed: int, seconds: float = 0.5, trace: int = 0):
    """The harness's whole run after its look for a chip: set-up, window,
    check, with a stand-in peak for the CPU.  Returns the result object it
    would print."""
    import jax

    from bench import run

    args = argparse.Namespace(workload=cell.name, seed=seed, seconds=seconds,
                              trace=trace)
    with mock.patch.object(core, "load_peaks",
                           lambda kind, *a: {"bf16_flops": 1e12}):
        result, _ = run.execute(cell, jax.devices()[:cell.chips], args,
                                core.load_runner(cell))
    return result


def run_on_devices(script: str, *argv: str, devices: int) -> dict:
    """``script`` in a fresh Python on ``devices`` virtual CPU devices, with
    the repo's root and ``src`` on its path.  Returns the JSON object of its
    last line of output."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    head = f"import sys; sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'src')!r}]\n"
    proc = subprocess.run([sys.executable, "-c", head + script, *argv], cwd=ROOT,
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
