import collections

import numpy as np

from bench import gen

MIX = [("dlrm", 0.4), ("bert", 0.3), ("candle", 0.2), ("vgg16", 0.1)]


def test_same_seed_same_events():
    big = 2**31 + 12345
    assert gen.resident_order(MIX, 4, big) == gen.resident_order(MIX, 4, big)
    orders = {tuple(gen.resident_order(MIX, 4, big + i)) for i in range(8)}
    assert len(orders) > 1


def test_every_seed_holds_the_same_mix():
    assert gen.apportion(MIX, 4) == ["dlrm", "dlrm", "bert", "candle"]
    assert collections.Counter(gen.apportion(MIX, 10)) == {
        "dlrm": 4, "bert": 3, "candle": 2, "vgg16": 1}
    for seed in (0, 1, 2**33 + 7):
        assert sorted(gen.resident_order(MIX, 4, seed)) == sorted(gen.apportion(MIX, 4))


def test_same_seed_same_batches():
    a = gen.token_batch(2**31 + 5, 3, 2, 16, 1000)
    b = gen.token_batch(2**31 + 5, 3, 2, 16, 1000)
    assert a.dtype == np.int32 and a.shape == (2, 16)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, gen.token_batch(2**31 + 5, 4, 2, 16, 1000))


def test_batches_are_the_program_pipeline_batches():
    from repro.configs.base import ShapeSpec, get_config
    from repro.data.pipeline import DataSpec, batch_for_step

    cfg = get_config("minicpm-2b").smoke()
    spec = DataSpec(cfg=cfg, shape=ShapeSpec("t", 16, 2, "train"), seed=2**31 + 9)
    for step in (0, 5):
        np.testing.assert_array_equal(
            batch_for_step(spec, step)["tokens"],
            gen.token_batch(2**31 + 9, step, 2, 16, cfg.vocab))


def test_small_seed_fits_31_bits():
    for seed in (0, 2**31 + 1, 2**40):
        s = gen.small_seed(seed)
        assert 0 <= s < 2**31
