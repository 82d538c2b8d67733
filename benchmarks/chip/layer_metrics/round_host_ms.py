"""Mean host ms per session round outside the selects and the engine calls
(ROUND ``wall_s`` - ``select_s`` - ``engine_s``), in the traced stretch: the
session's self time, its per-token bookkeeping and callbacks among it."""
from _counters import mean_ms, stretch


def read(run):
    return mean_ms(e.data["wall_s"] - e.data["select_s"] - e.data["engine_s"]
                   for e in stretch(run, "round", "wall_s"))
