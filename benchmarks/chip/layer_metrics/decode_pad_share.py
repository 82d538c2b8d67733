"""Share of the decode steps' lanes that are padding, in percent: the sum
over the traced stretch's DECODE_STEPs of (``bucket`` - ``batch``) over the
sum of ``bucket``, the power-of-two batch each step runs at."""
from _counters import stretch


def read(run):
    steps = stretch(run, "decode_step", "bucket")
    lanes = sum(e.data["bucket"] for e in steps)
    if not lanes:
        return None
    return 100.0 * sum(e.data["bucket"] - e.data["batch"] for e in steps) / lanes
