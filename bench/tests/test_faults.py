"""Whole runs (set-up, window, check; everything but the look for a chip)
with the timed path broken underneath: ``correct`` comes out false."""

import pytest

from .helpers import run_cell, small_cell


def _wrap_train_step(monkeypatch, fault):
    import repro.train.steps as steps

    real = steps.jit_train_step

    def broken(*a, **kw):
        if fault == "unchanged":
            kw["donate"] = False  # the state it returns is the state it got
        jitted, rest = real(*a, **kw)

        def step(params, opt_state, batch, i):
            if fault == "half_batch":
                tokens = batch["tokens"]
                batch = dict(batch, tokens=tokens[: tokens.shape[0] // 2])
            new_p, new_s, metrics = jitted(params, opt_state, batch, i)
            if fault == "unchanged":
                return params, opt_state, metrics
            if fault == "loss_altered":
                metrics = dict(metrics, loss=metrics["loss"] * 1.01)
            return new_p, new_s, metrics

        return step, rest

    monkeypatch.setattr(steps, "jit_train_step", broken)


def test_train_sound_run_is_correct():
    res = run_cell(small_cell("train.minicpm-2b.s1024"), 2**31 + 23)
    assert res["correct"] is True
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "loss_altered"])
def test_train_fault_is_caught(monkeypatch, fault):
    _wrap_train_step(monkeypatch, fault)
    res = run_cell(small_cell("train.minicpm-2b.s1024"), 2**31 + 29)
    assert res["correct"] is False


@pytest.mark.parametrize("fault", ["energies", "reported_price", "no_replan"])
def test_admit_fault_is_caught(monkeypatch, fault):
    import repro.core.alternating as alternating
    import repro.core.online as online
    import repro.core.planeval_jax as pj

    if fault == "energies":
        real = pj.ChainKernel.run_grid

        def run_grid(self, *a, **kw):
            ba, bo, hist = real(self, *a, **kw)
            return ba, bo * (1.0 + 1e-3), hist

        monkeypatch.setattr(pj.ChainKernel, "run_grid", run_grid)
    elif fault == "reported_price":
        real_fused = alternating._co_optimize_fused

        def fused(*a, **kw):
            plan = real_fused(*a, **kw)
            plan.iter_time *= 1.0 + 1e-3
            return plan

        monkeypatch.setattr(alternating, "_co_optimize_fused", fused)
    else:
        monkeypatch.setattr(online.JobSetController, "replan",
                            lambda self, now, trigger: None)
    res = run_cell(small_cell("admit.shared432.churn"), 2**31 + 31)
    assert res["correct"] is False
