"""Shared by the readers of the program's own counters (``repro.obs``
events): the events of the traced stretch, the session time between the end
of the profiler's start stall and the start of its stop stall."""


def stretch(run, kind, key):
    """Events of type ``kind`` (the event type's value) in the traced stretch
    whose data carry ``key``; none where the run has no events or no pair of
    stalls, or the program does not count ``key``."""
    if run.events is None or len(run.stalls) < 2:
        return []
    lo, hi = run.stalls[0][1], run.stalls[1][0]
    return [e for e in run.events
            if e.type.value == kind and lo <= e.t < hi and key in e.data]


def mean_ms(values):
    values = list(values)
    return 1e3 * sum(values) / len(values) if values else None
