"""Mean host ms per session round in both schedulers' ``select`` calls
(ROUND ``select_s``), in the traced stretch, as the program counts it."""
from _counters import mean_ms, stretch


def read(run):
    return mean_ms(e.data["select_s"] for e in stretch(run, "round", "select_s"))
