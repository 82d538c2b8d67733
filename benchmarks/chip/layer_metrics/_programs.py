"""Shared by the device-time readers: which traced programs are which
serving step, the work of the traced calls, and the roofline arithmetic
over matched calls."""
from _counters import stretch

# program (XLA module) names of the engine's jitted steps
CHUNK = ("jit_chunk_prefill_step",)
DECODE = ("jit__slot_step", "jit__page_step")


def times(run, names):
    """Device seconds per execution of the named programs, in order."""
    if run.trace is None:
        return []
    for n in names:
        if n in run.trace.modules:
            return run.trace.modules[n]
    return []


def chunk_work(run):
    """(FLOPs, bytes) of each chunk-prefill call of the traced stretch, from
    the program's PREFILL_CHUNK counters (``start``, ``take``)."""
    return [run.arch.chunk_work(run.spec, e.data["start"], e.data["take"])
            for e in stretch(run, "prefill_chunk", "take")]


def decode_work(run):
    """(FLOPs, bytes) of each decode step of the traced stretch, from the
    program's DECODE_STEP counters (each live lane's ``positions``)."""
    return [run.arch.decode_work(run.spec, e.data["positions"])
            for e in stretch(run, "decode_step", "positions")]


def matched(run, names, works):
    """(work, device seconds) pairs: the k-th traced call with the k-th
    traced execution (a barrier before and after the traced seconds keeps
    calls and executions in step)."""
    t = times(run, names)
    n = min(len(t), len(works))
    return list(zip(works[:n], t[:n], strict=True))


def roofline(run, names, works):
    """Least time of the calls' work over their device time, in percent."""
    from work import least_time

    pairs = matched(run, names, works)
    if run.peak is None or not pairs:
        return None
    least = sum(least_time(f, b, run.peak)[0] for (f, b), _ in pairs)
    return 100.0 * least / sum(t for _, t in pairs)


def mfu(run, names, works):
    """The calls' model FLOPs over device time times the chip's peak, in percent."""
    pairs = matched(run, names, works)
    if run.peak is None or not pairs:
        return None
    flops = sum(f for (f, _), _ in pairs)
    return 100.0 * flops / (sum(t for _, t in pairs) * run.peak["flops"])
