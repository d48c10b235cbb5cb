"""The four-chip cell on four virtual CPU devices: a sound run is correct,
and a run with its timed path broken underneath is not: the gradient
exchange between chips left out, the state returned unchanged, or half of
the batch left out."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = """
import json, sys
sys.path.insert(0, {src!r}); sys.path.insert(0, {root!r})
from bench.tests.helpers import run_cell, small_cell

class Patch:
    def setattr(self, obj, name, value):
        setattr(obj, name, value)

import repro.train.steps as steps
mode = sys.argv[1]
if mode == "no_exchange":
    steps.topoopt_psum_fn = lambda *a, **kw: (lambda g: g * 4)
elif mode in ("unchanged", "half_batch"):
    import jax.numpy as jnp
    real = steps.make_shardmap_dp_train_step

    def broken(*a, **kw):
        jitted = real(*a, **kw)

        def step(params, opt_state, batch, i, residual):
            if mode == "half_batch":  # the first half, twice: its mean
                t = batch["tokens"]
                h = t.shape[0] // 2
                batch = dict(batch, tokens=jnp.concatenate([t[:h], t[:h]]))
            out = jitted(params, opt_state, batch, i, residual)
            return (params, opt_state, *out[2:]) if mode == "unchanged" else out
        return step

    steps.make_shardmap_dp_train_step = broken
res = run_cell(small_cell("sync.minicpm-2b-dp4.ring"), 2**31 + 43, seconds=0.3,
               monkeypatch=Patch())
print(json.dumps({{"correct": res["correct"], "count": res["device"]["count"]}}))
"""


def _run(mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(src=str(ROOT / "src"), root=str(ROOT))
    proc = subprocess.run([sys.executable, "-c", code, mode], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct():
    assert _run("sound") == {"correct": True, "count": 4}


def test_exchange_left_out_is_caught():
    assert _run("no_exchange") == {"correct": False, "count": 4}


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_sync_fault_is_caught(fault):
    assert _run(fault) == {"correct": False, "count": 4}
