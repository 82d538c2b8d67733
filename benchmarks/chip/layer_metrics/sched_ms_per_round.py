"""Host time of both schedulers' ``select`` calls per session round inside
the window, in ms, from the benchmark's wrappers."""

NAMES = ("prefill_sched.select", "decode_sched.select")


def read(run):
    if run.rounds == 0 or any(n in run.spans.missing for n in NAMES):
        return None
    lo, hi = run.window
    return 1e3 * sum(run.spans.total(n, lo, hi) for n in NAMES) / run.rounds
