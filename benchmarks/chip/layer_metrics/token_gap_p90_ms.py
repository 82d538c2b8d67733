"""90th percentile, in ms, of every gap between two consecutive tokens of
the counted requests, on the driver's clock: ``itl_p90_ms`` read in a traced
run, for a cell where that tail is too unsteady to hold end to end. Gaps
that span the profiler's start or stop (which stall the loop while the trace
is written) are left out."""


def read(run):
    from stats import percentile

    gaps = [
        b - a
        for r in run.timed
        for a, b in zip(r.times, r.times[1:])
        if not any(a < s1 and s0 < b for s0, s1 in run.stalls)
    ]
    return 1e3 * percentile(gaps, 90) if gaps else None
