"""Host milliseconds per step blocked in the data pipeline's
``Prefetcher.next()``."""


def read(run):
    if "data_wait" not in run.spans or not run.units:
        return None
    return 1e3 * run.spans["data_wait"] / run.units
