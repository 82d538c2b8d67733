"""Mean device time of one decode_step program execution in the traced seconds,
in ms, from the profiler trace."""
from _programs import DECODE, times


def read(run):
    t = times(run, DECODE)
    return 1e3 * sum(t) / len(t) if t else None
