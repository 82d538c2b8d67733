"""90th percentile of the counted requests' queue wait, in ms: from the
session's SUBMIT event (stamped with the request's due time) to its
PREFILL_START, both on the session clock. Requests whose wait spans the
profiler's start or stop (which stall the loop while the trace is written)
are left out."""


def read(run):
    if run.events is None:
        return None
    from stats import percentile

    counted = {r.rid for r in run.counted}
    submit, start = {}, {}
    for ev in run.events:
        if ev.rid not in counted:
            continue
        if ev.type.value == "submit":
            submit[ev.rid] = ev.t
        elif ev.type.value == "prefill_start":
            start.setdefault(ev.rid, ev.t)
    waits = [
        start[r] - submit[r] for r in start
        if r in submit and not any(a < start[r] and submit[r] < b for a, b in run.stalls)
    ]
    return 1e3 * percentile(waits, 90) if waits else None
