"""Mean device time of one prefill_chunk program execution in the traced seconds,
in ms, from the profiler trace."""
from _programs import CHUNK, times


def read(run):
    t = times(run, CHUNK)
    return 1e3 * sum(t) / len(t) if t else None
