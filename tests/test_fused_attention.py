"""The fused self-attention path (``kernels.ops.fused_attention`` through
``models.layers.attention``): values and gradients against ``_sdpa`` in the
interpreter, and which path each input takes, by the counters it leaves on
the innermost ``repro.telemetry`` span.

The fused path runs only on the TPU; tests stand in for it by patching
``layers._on_tpu``, and the kernel then runs in Pallas' interpreter."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_with_devices
from repro import telemetry
from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models import layers as L
from repro.models import lm
from repro.optim import adamw, cosine
from repro.parallel import act_sharding
from repro.parallel.sharding import ShardingPlan
from repro.train.steps import jit_train_step

# query heads, key heads, causal, window
CASES = {
    "causal-mha": (4, 4, True, 0),
    "causal-gqa": (4, 2, True, 0),
    "window": (4, 2, True, 96),
    "bidirectional": (4, 4, False, 0),
}


def _cfg(heads=4, kv_heads=2, d=128, hd=64, layers=2):
    return ArchConfig(name="fused-test", family="dense", n_layers=layers,
                      d_model=d, n_heads=heads, n_kv_heads=kv_heads,
                      d_ff=2 * d, vocab=256, head_dim=hd,
                      param_dtype="bfloat16", activation_dtype="bfloat16")


@pytest.fixture
def no_policy():
    saved = act_sharding.get_policy()
    act_sharding.set_policy(None)
    yield
    act_sharding.set_policy(saved)


def _rel(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _qkv(heads, kv_heads, seq=256, hd=64, batch=2):
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (batch, seq, heads, hd), jnp.float32)
    k = jax.random.normal(ks[1], (batch, seq, kv_heads, hd), jnp.float32)
    v = jax.random.normal(ks[2], (batch, seq, kv_heads, hd), jnp.float32)
    do = jax.random.normal(ks[3], (batch, seq, heads, hd), jnp.float32)
    return q, k, v, do


def _sdpa_bshd(q, k, v, causal, window):
    """``layers._sdpa`` with the mask ``layers.attention`` builds, in the
    (B, S, H, D) layout."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    mask = L.causal_mask(S, S, window=window) if causal else None
    out = L._sdpa(q.reshape(B, S, KV, H // KV, D), k, v, mask)
    return out.reshape(B, S, H, D)


def _fused_bshd(q, k, v, causal, window):
    scale = 1.0 / math.sqrt(q.shape[-1])
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    out = ops.fused_attention(*(a.transpose(0, 2, 3, 1) for a in (q, k, v)),
                              causal=causal, window=window, interpret=True)
    return out.transpose(0, 2, 1, 3)


def _value_and_grads(fn, q, k, v, do):
    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * do)

    return fn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_kernel_matches_sdpa(case):
    """Output and the gradients of q, k and v at S 256, d 64: the kernel
    on bf16 inputs lies as close to float32 ``_sdpa`` as ``_sdpa`` on the
    same bf16 inputs does (its scores stay float32 where ``_sdpa`` rounds
    them to bf16), and within 1e-2 of it."""
    heads, kv_heads, causal, window = CASES[case]
    q, k, v, do = _qkv(heads, kv_heads)
    bf = [a.astype(jnp.bfloat16) for a in (q, k, v)]
    ref = _value_and_grads(
        lambda *a: _sdpa_bshd(*a, causal, window), q, k, v, do)
    fused = _value_and_grads(
        lambda *a: _fused_bshd(*a, causal, window), *bf, do)
    xla = _value_and_grads(
        lambda *a: _sdpa_bshd(*a, causal, window), *bf, do)
    for name, r, f, x in zip(("out", "dq", "dk", "dv"),
                             [ref[0], *ref[1]], [fused[0], *fused[1]],
                             [xla[0], *xla[1]]):
        err = _rel(f, r)
        assert err < 1e-2, (name, err)
        assert err <= 1.1 * _rel(x, r), (name, err, _rel(x, r))


def test_fused_kernel_window_is_causal_only():
    q, k, v, _ = _qkv(4, 4)
    with pytest.raises(ValueError, match="causal"):
        ops.fused_attention(q, k, v, causal=False, window=16, interpret=True)


def _attend(cfg, x, **kw):
    p = L.init_attention(jax.random.PRNGKey(0), cfg)

    def f(p, x):
        return L.attention(p, x, cfg, **kw).astype(jnp.float32)

    with telemetry.span("test.attend") as sp:
        out = f(p, x)
        grads = jax.grad(lambda p, x: jnp.sum(f(p, x) ** 2),
                         argnums=(0, 1))(p, x)
    return out, grads, sp.counts


def _x(seq, d=128, batch=2):
    return jax.random.normal(jax.random.PRNGKey(1), (batch, seq, d),
                             jnp.float32).astype(jnp.bfloat16)


@pytest.mark.parametrize("case", sorted(CASES) + ["bidirectional-no-rope"])
def test_attention_takes_the_fused_path(case, monkeypatch, no_policy):
    """On the TPU, aligned self-attention with no explicit mask runs the
    fused kernel: the same output and gradients (of x and of every weight)
    as the XLA path, and only ``attention.fused`` counted.  The last case
    is the audio encoder's: no RoPE."""
    use_rope = case != "bidirectional-no-rope"
    heads, kv_heads, causal, window = CASES[case if use_rope else "bidirectional"]
    cfg = _cfg(heads, kv_heads)
    x = _x(256)
    kw = dict(causal=causal, window=window, use_rope=use_rope)
    monkeypatch.setattr(L, "_on_tpu", lambda: False)
    xla = _attend(cfg, x, **kw)
    monkeypatch.setattr(L, "_on_tpu", lambda: True)
    fused = _attend(cfg, x, **kw)
    assert xla[2] == {"attention.xla": 2}
    assert fused[2] == {"attention.fused": 2}
    assert _rel(fused[0], xla[0]) < 1e-2
    gx, gp = fused[1][1], fused[1][0]
    assert _rel(gx, xla[1][1]) < 2e-2
    for name in ("wq", "wk", "wv", "wo"):
        assert _rel(gp[name], xla[1][0][name]) < 2e-2, name


@pytest.mark.parametrize("why", ["cpu", "unaligned", "cross", "mask",
                                 "window-bidirectional", "seq-sharded"])
def test_attention_takes_the_xla_path(why, monkeypatch, no_policy):
    """Everything the fused kernel does not take counts ``attention.xla``:
    the CPU, a sequence that is not a multiple of the kernel's block,
    cross-attention, an explicit mask, a bidirectional window, and a
    sequence sharded by the activation policy."""
    cfg = _cfg()
    monkeypatch.setattr(L, "_on_tpu", lambda: why != "cpu")
    kw = {}
    seq = 200 if why == "unaligned" else 256
    if why == "cross":
        kw = dict(kv_x=_x(128), use_rope=False)
    elif why == "mask":
        kw = dict(mask=L.causal_mask(seq, seq))
    elif why == "window-bidirectional":
        kw = dict(causal=False, window=64)
    elif why == "seq-sharded":
        mesh = jax.make_mesh((1,), ("model",))
        act_sharding.set_policy(act_sharding.ActivationPolicy(
            dp=None, tp=None, seq="model", mesh=mesh))
    _, _, counts = _attend(cfg, _x(seq), **kw)
    assert counts == {"attention.xla": 2}


def test_loss_and_grads_of_a_model_match_on_both_paths(monkeypatch, no_policy):
    """A whole dense model through ``lm.loss_fn`` under remat: the loss and
    every gradient agree between the paths, and only the path taken is
    counted."""
    cfg = _cfg(layers=2)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(3), (2, 256), 0, 256)}

    def run(fused):
        monkeypatch.setattr(L, "_on_tpu", lambda: fused)
        with telemetry.span("test.model") as sp:
            (loss, _), grads = jax.value_and_grad(
                lambda p: lm.loss_fn(p, batch, cfg, remat="full"),
                has_aux=True)(params)
        return float(loss), grads, sp.counts

    loss_f, grads_f, counts_f = run(True)
    loss_x, grads_x, counts_x = run(False)
    assert set(counts_f) == {"attention.fused"} and counts_f["attention.fused"] >= 1
    assert set(counts_x) == {"attention.xla"}
    assert abs(loss_f - loss_x) / abs(loss_x) < 1e-3
    for (path, gf), gx in zip(jax.tree_util.tree_leaves_with_path(grads_f),
                              jax.tree.leaves(grads_x)):
        if float(jnp.linalg.norm(gx.astype(jnp.float32))) > 0:
            assert _rel(gf, gx) < 3e-2, jax.tree_util.keystr(path)


@pytest.mark.parametrize("fused", [False, True])
def test_first_dispatch_counts_the_path(fused, monkeypatch):
    """The ``train.dispatch`` record of the call that traced a jitted step
    (one-device mesh, the activation policy of ``jit_train_step``) counts
    the path its attention took, and the later calls count nothing."""
    monkeypatch.setattr(L, "_on_tpu", lambda: fused)
    saved = act_sharding.get_policy()
    cfg = _cfg(layers=1)
    mesh = jax.make_mesh((1,), ("data",))
    opt = adamw(cosine(1e-3, 10))
    try:
        step, (_p, _o, p_sh, o_sh, _b) = jit_train_step(
            cfg, opt, ShardingPlan(fsdp=False), mesh)
        params = jax.jit(lambda: lm.init(jax.random.PRNGKey(0), cfg),
                         out_shardings=p_sh)()
        state = jax.jit(opt.init, out_shardings=o_sh)(params)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 256)
        batch = {"tokens": tokens}
        losses = []
        for i in range(2):
            with mesh:
                params, state, metrics = step(params, state, batch, jnp.int32(i))
            losses.append(float(metrics["loss"]))
    finally:
        act_sharding.set_policy(saved)
    first, second = telemetry.recent("train.dispatch", 2)
    name = "attention.fused" if fused else "attention.xla"
    other = "attention.xla" if fused else "attention.fused"
    assert first.counts.get(name, 0) > 0 and other not in first.counts
    assert second.counts == {"train.compiles": 0}
    assert all(math.isfinite(v) for v in losses)


def test_fused_path_runs_per_shard_on_a_mesh():
    """On four devices the fused kernel runs under shard_map, one shard per
    device: the batch over ``data`` of the activation policy (and, on a
    2 x 2 mesh, heads over ``model``) on a mesh of automatic axes, and the
    batch sharding of the arrays' own types on a mesh of explicit axes.
    The loss and gradients match the XLA path's, and the compiled fused
    step gathers nothing."""
    code = r'''
import contextlib
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs.base import ArchConfig
from repro.models import layers as L, lm
from repro.parallel import act_sharding
from repro.parallel.act_sharding import ActivationPolicy
cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=128, n_heads=4,
                 n_kv_heads=2, d_ff=256, vocab=256, head_dim=64,
                 param_dtype="bfloat16", activation_dtype="bfloat16")
params = lm.init(jax.random.PRNGKey(0), cfg)
tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 128), 0, 256)
x = jax.random.normal(jax.random.PRNGKey(2), (4, 128, 128)).astype(jnp.bfloat16)

def model_loss(p, b):  # the whole model, activations placed by the policy
    return lm.loss_fn(p, {"tokens": b}, cfg, remat="full")[0]

def layer_loss(p, b):  # one attention layer on arrays typed data-sharded
    out = L.attention(p["blocks"]["attn"], b, cfg)
    return jnp.sum(out.astype(jnp.float32) ** 2)

auto, explicit = AxisType.Auto, AxisType.Explicit
for shape, names, kind, tp in [((4,), ("data",), auto, None),
                               ((2, 2), ("data", "model"), auto, "model"),
                               ((4,), ("data",), explicit, None)]:
    mesh = jax.make_mesh(shape, names, axis_types=(kind,) * len(shape))
    policy = ActivationPolicy(dp="data", tp=tp, mesh=mesh)
    act_sharding.set_policy(policy if kind == auto else None)
    p = params
    if kind == explicit:
        p = jax.tree.map(lambda a: a[0], params)  # one layer's weights
    p = jax.device_put(p, NamedSharding(mesh, P()))
    b = jax.device_put(tokens if kind == auto else x,
                       NamedSharding(mesh, P("data")))
    loss = model_loss if kind == auto else layer_loss
    out = {}
    for fused in (False, True):
        L._on_tpu = lambda fused=fused: fused
        f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1) if kind == explicit else 0))
        with jax.set_mesh(mesh) if kind == explicit else contextlib.nullcontext():
            hlo = f.lower(p, b).compile().as_text()
            out[fused] = f(p, b)
        if fused:
            print(names, kind, "all-gather" in hlo)
    (lx, gx), (lf, gf) = out[False], out[True]
    assert abs(float(lf) - float(lx)) / abs(float(lx)) < 1e-3
    for a, c in zip(jax.tree.leaves(gf), jax.tree.leaves(gx)):
        a, c = np.asarray(a, np.float32), np.asarray(c, np.float32)
        n = np.linalg.norm(c)
        assert n == 0 or np.linalg.norm(a - c) / n < 3e-2
print("OK")
'''
    out = run_with_devices(code, n_devices=4)
    lines = out.strip().splitlines()
    assert lines[-1] == "OK" and len(lines) == 4, out
    for line in lines[:-1]:
        assert line.endswith("False"), line  # no all-gather
