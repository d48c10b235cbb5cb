"""Int8 gradient compression: quantizer bounds + compressed allreduce
accuracy + error-feedback convergence (subprocess, 8 devices)."""

import numpy as np
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from _subproc import run_with_devices
from repro.parallel.compression import dequantize_block, quantize_block

import pytest

# Multi-minute subprocess tests (fresh jax init per case); quick loop:
# python -m pytest -m "not slow"
pytestmark = pytest.mark.slow


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.array(rng.standard_normal(5000), jnp.float32)
    q, s, size = quantize_block(x, block=256)
    deq = dequantize_block(q, s)[:5000]
    err = np.abs(np.asarray(deq) - np.asarray(x))
    # per-block max-scale quantization: |err| <= scale/2 = max|x_block|/254
    blocks = np.asarray(x)
    assert err.max() <= np.abs(blocks).max() / 254 + 1e-7


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=1, max_value=3000), st.integers(min_value=8, max_value=512))
def test_quantize_shapes_property(n, block):
    rng = np.random.default_rng(n)
    x = jnp.array(rng.standard_normal(n), jnp.float32)
    q, s, size = quantize_block(x, block=block)
    assert q.shape[0] * q.shape[1] >= n
    assert q.shape[1] == block
    deq = dequantize_block(q, s)
    rel = np.abs(np.asarray(deq[:n]) - np.asarray(x))
    scale_bound = np.abs(np.asarray(x)).max() / 127 + 1e-7
    assert rel.max() <= scale_bound


def test_compressed_allreduce_close_to_exact():
    out = run_with_devices(
        """
import jax, numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
try:
    from jax import shard_map
except ImportError:
    from jax.experimental.shard_map import shard_map
from repro.parallel.compression import compressed_ring_all_reduce

mesh = jax.make_mesh((8,), ("x",))
rng = np.random.default_rng(0)
x = jnp.array(rng.standard_normal((8, 300)), jnp.float32)

def fn(v):
    out, res = compressed_ring_all_reduce(v, "x", p=3, block=64)
    return out

out = jax.jit(shard_map(fn, mesh=mesh, in_specs=P("x"), out_specs=P("x")))(x)
exact = np.asarray(x).sum(axis=0)
# per-hop int8 error is relative to the block max, so measure absolute error
# against the payload scale (near-zero sums make per-element ratios blow up).
scale = np.abs(np.asarray(x)).max()
err = np.abs(np.asarray(out)[0] - exact).max()
assert err < 0.1 * scale * 8, (err, scale)  # 2(n-1)/254 ~ 5.5% of max
print("PASS", err / scale)
""",
        n_devices=8,
    )
    assert "PASS" in out


def test_error_feedback_converges_on_quadratic():
    """SGD with compressed gradients + error feedback must still drive a
    quadratic to its minimum (EF-SGD guarantee)."""
    out = run_with_devices(
        """
import jax, numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.parallel.compression import Compressor
mesh = jax.make_mesh((8,), ("x",))
rng = np.random.default_rng(0)
target = jnp.array(rng.standard_normal(64), jnp.float32)
comp = Compressor(block=32)

def make_step():
    def step(w, residual, noise):
        g = (w - target) + 0.01 * noise[0]  # per-device noisy grad
        g_sync, new_res = comp.sync({"w": g}, {"w": residual[0]}, "x",
                                    strides=(1, 3))
        return w - 0.3 * g_sync["w"], new_res["w"][None]
    return jax.jit(jax.shard_map(step, mesh=mesh,
                                 in_specs=(P(), P("x"), P("x")),
                                 out_specs=(P(), P("x")),
                                 check_vma=False))

step = make_step()
w = jnp.zeros(64)
res = jnp.zeros((8, 64))
for i in range(60):
    noise = jnp.array(rng.standard_normal((8, 64)), jnp.float32)
    w, res = step(w, res, noise)
final = float(jnp.linalg.norm(w - target))
assert final < 0.05, final
print("PASS", final)
""",
        n_devices=8,
    )
    assert "PASS" in out
