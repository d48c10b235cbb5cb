"""Traffic generation from ``--seed``: the one general generator every
traffic file feeds.  The program under test receives only what these
functions return.

Every seed gets the same work in another order: a cluster's resident job
types apportion the mix exactly, and the seed draws only their order.
"""

from __future__ import annotations

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A NumPy generator for one named stream of one seed; any whole seed,
    however large."""
    return np.random.default_rng([abs(int(seed)), *stream])


def small_seed(seed: int, stream: int = 0) -> int:
    """A 31-bit seed for APIs that take a machine integer."""
    return int(rng(seed, 7, stream).integers(0, 2**31 - 1))


def apportion(mix: list, slots: int) -> list[str]:
    """``slots`` job types in the mix's shares, by largest remainder."""
    quota = [(name, share * slots) for name, share in mix]
    counts = {name: int(q) for name, q in quota}
    left = slots - sum(counts.values())
    for name, q in sorted(quota, key=lambda nq: -(nq[1] - int(nq[1])))[:left]:
        counts[name] += 1
    return [name for name, _ in mix for _ in range(counts[name])]


def resident_order(mix: list, slots: int, seed: int) -> list[str]:
    """The resident types of a cluster with ``slots`` tenants, in an order
    drawn from the seed."""
    names = apportion(mix, slots)
    return [names[i] for i in rng(seed, 2).permutation(slots)]


def token_batch(seed: int, step: int, batch: int, seq_len: int,
                vocab: int) -> np.ndarray:
    """The tokens of one training step: uniform ids, ``(batch, seq_len)``
    int32.  The same formula as the program's synthetic data pipeline
    (``repro.data.pipeline.batch_for_step``), kept here so that the
    reference regenerates the batches the program trained on."""
    g = np.random.default_rng(np.uint64(seed * 1_000_003 + step))
    return g.integers(0, vocab, (batch, seq_len), dtype=np.int32)
