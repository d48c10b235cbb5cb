"""The four-chip cell on four virtual CPU devices: a sound run is correct,
and a run with its timed path broken underneath is not: the gradient
exchange between chips left out, the state returned unchanged, or half of
the batch left out."""

import pytest

from .helpers import run_on_devices

SCRIPT = """
import json, sys
from bench.tests.helpers import run_cell, small_cell

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
res = run_cell(small_cell("sync.minicpm-2b-dp4.ring"), 2**31 + 43, seconds=0.3)
print(json.dumps({"correct": res["correct"], "count": res["device"]["count"]}))
"""


def _run(mode):
    return run_on_devices(SCRIPT, mode, devices=4)


def test_sound_run_is_correct():
    assert _run("sound") == {"correct": True, "count": 4}


def test_exchange_left_out_is_caught():
    assert _run("no_exchange") == {"correct": False, "count": 4}


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_sync_fault_is_caught(fault):
    assert _run(fault) == {"correct": False, "count": 4}
