"""Mean host ms per session round in both schedulers' ``select`` calls
(ROUND ``select_s``), in the traced stretch: the program's own twin of
``sched_ms_per_round``."""
from _counters import mean_ms, stretch


def read(run):
    return mean_ms(e.data["select_s"] for e in stretch(run, "round", "select_s"))
