"""Executables the window's step dispatches added to the jit's cache
(compiled, or loaded from the persistent cache): the ``train.compiles``
counter on each ``train.dispatch`` record, summed."""

from bench.program_spans import window_records


def read(run):
    records = window_records("train.dispatch", run.units)
    if records is None:
        return None
    return sum(r.counts.get("train.compiles", 0) for r in records)
