"""chip_smoke.py's phases at tiny sizes on the CPU, and its refusal to run
without a TPU.  The script itself only runs on the chip; these tests keep
its paths, arguments and checks working between chip runs."""

from __future__ import annotations

import dataclasses
import os
import shutil
import subprocess
import sys

import pytest
from _subproc import run_with_devices

import chip_smoke
from repro.configs.base import get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_lm():
    return dataclasses.replace(get_config(chip_smoke.MODEL).smoke(), n_layers=2)


def test_phase_train_tiny():
    out = chip_smoke.phase_train(_tiny_lm(), seq_len=32, batch=2, steps=3,
                                 log=lambda *_: None)
    assert out["layers"] == 2 and len(out["losses"]) == 3


def test_phase_planner_tiny():
    out = chip_smoke.phase_planner(
        n=32, degree=4, job_size=4, resident=3, arrivals=2, departures=1,
        failures=1, n_iters=4,
    )
    assert out["fused_dispatches"] >= 1 and out["arrivals_adopted"] >= 1
    assert out["optimizer_errors"] == 0 and out["replans"] >= 1
    assert out["widest_grid"][0] == 4  # four placement candidates


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_main_fails_without_tpu(tmp_path, where):
    """No TPU, or no repository beside the script: non-zero exit and no
    result line."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if where == "alone":
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=300, env=env, cwd=tmp_path,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_dir(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the cache sits at
    a fixed, gitignored path in the checkout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from repro.launch.compile_cache import enable_compile_cache\n"
         "print(enable_compile_cache())\n"
         "print(jax.config.jax_compilation_cache_dir)\n"],
        capture_output=True, text=True, timeout=120, env=env, cwd=tmp_path,
    )
    assert proc.returncode == 0, proc.stderr
    returned, configured = proc.stdout.split()
    want = (str(tmp_path / env_dir) if env_dir
            else os.path.join(ROOT, ".jax_cache"))
    assert returned == configured == want
    if not env_dir:
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()


def test_four_chip_sync_on_host_devices():
    out = run_with_devices(
        f"""
import dataclasses, sys
sys.path.insert(0, {ROOT!r})
import chip_smoke
from repro.configs.base import get_config
cfg = dataclasses.replace(get_config("minicpm-2b").smoke(), n_layers=2)
rows = chip_smoke.four_chip_allreduce((4 << 10, 1 << 16))
assert len(rows) == 6 and all(r["equal"] for r in rows)
losses = chip_smoke.four_chip_train(cfg, seq_len=32, steps=2)
assert set(losses) == {{"psum", "multi_ring(1,3)", "recursive_hd",
                       "multi_tree(1,3)"}}
print("PASS")
""",
        n_devices=4,
    )
    assert "PASS" in out
