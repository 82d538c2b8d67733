"""Mean host ms per decode step blocked until its tokens are on the host
(DECODE_STEP ``sync_s``), in the traced stretch. Read beside
``decode_step_ms``: the difference is the sampler plus the copy back."""
from _counters import mean_ms, stretch


def read(run):
    return mean_ms(e.data["sync_s"] for e in stretch(run, "decode_step", "sync_s"))
