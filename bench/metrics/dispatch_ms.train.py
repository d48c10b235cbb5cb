"""Host milliseconds per window step in the program's ``train.dispatch``
span: the launch of the jitted step (``repro.train.steps.RecordedStep``),
from the call until it returns, before the loss is read back."""

from bench.program_spans import window_records


def read(run):
    records = window_records("train.dispatch", run.units)
    if records is None:
        return None
    return 1e3 * sum(r.seconds for r in records) / len(records)
