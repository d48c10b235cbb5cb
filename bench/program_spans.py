"""The program's own spans (``repro.telemetry``) as the per-layer readers
take them: the records of one span over a run's window.

The window's steps are the last ones that dispatch a step or take a batch,
so the newest ``units`` records of such a span are the window's.  A
program without the recorder gives nothing, and so does one that kept
fewer records of the span than the window has steps.
"""


def window_records(name: str, units: int):
    """The newest ``units`` records of span ``name``, or None."""
    try:
        from repro import telemetry
    except ImportError:
        return None
    if not units:
        return None
    records = telemetry.recent(name, units)
    return records if len(records) == units else None
