"""Mean host ms per decode step from entry into ``DecodeEngine.step`` until
its step program and the sampler are dispatched (DECODE_STEP ``launch_s``),
in the traced stretch: the time the chip waits for the host before each
step."""
from _counters import mean_ms, stretch


def read(run):
    return mean_ms(e.data["launch_s"] for e in stretch(run, "decode_step", "launch_s"))
