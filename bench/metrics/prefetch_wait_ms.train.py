"""Host milliseconds per window step in the program's ``data.wait`` span,
inside ``repro.data.pipeline.Prefetcher.next()``: the in-program twin of
``data_wait_ms.train``."""

from bench.program_spans import window_records


def read(run):
    records = window_records("data.wait", run.units)
    if records is None:
        return None
    return 1e3 * sum(r.seconds for r in records) / len(records)
