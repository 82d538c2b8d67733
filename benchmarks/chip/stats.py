"""Per-request latencies, percentiles and SLO attainment over the counted
requests, from the driver's own clock.

Each counted request is known by its due time, its token count, its two
limits and the times at which its tokens reached the driver's ``on_token``
callback. Its time to first token (TTFT) is the first time less the due
time; its time per output token (TPOT) is the last time less the first over
the tokens after the first. The attainment rule is that of
``repro.obs.slo`` / ``core.request``, restated here: a request meets its SLO
when it got every token it asked for, its TTFT is within its TTFT limit and
its TPOT within its TPOT limit; a request that failed or did not finish
misses both.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Sequence, Tuple

import numpy as np


@dataclass
class Timed:
    """A counted request as the driver sees it."""

    due: float  # due time on the driver's clock
    n_out: int  # tokens asked for
    ttft_limit: float  # seconds
    tpot_limit: float  # seconds
    times: List[float] = field(default_factory=list)  # each token's arrival at the driver

    @property
    def finished(self) -> bool:
        return len(self.times) == self.n_out


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile q (0-100) of the values."""
    return float(np.percentile(np.asarray(values, float), q))


def latencies(reqs: Sequence[Timed], end: float) -> Tuple[List[float], List[float], List[bool], int]:
    """Per request: TTFT and TPOT in seconds, whether it met both limits,
    and the number that failed. A request that did not get all its tokens
    failed: its TTFT is its first token's time (or the end of the run, `end`)
    less its due time, and its TPOT the time from its first token to the end
    of the run over the tokens it got after the first (or the same wait
    where it got none): lower bounds, so it sorts last."""
    ttft, tpot, met, failed = [], [], [], 0
    for r in reqs:
        if r.finished:
            a = r.times[0] - r.due
            b = (r.times[-1] - r.times[0]) / (r.n_out - 1) if r.n_out > 1 else 0.0
            ttft.append(a)
            tpot.append(b)
            met.append(a <= r.ttft_limit and b <= r.tpot_limit)
            continue
        failed += 1
        met.append(False)
        if not r.times:
            ttft.append(end - r.due)
            tpot.append(end - r.due)
        else:
            ttft.append(r.times[0] - r.due)
            tpot.append((end - r.times[0]) / max(1, len(r.times) - 1))
    return ttft, tpot, met, failed


def token_gaps(reqs: Sequence[Timed], end: float) -> List[float]:
    """Every gap between two consecutive tokens of a request, over all the
    requests: sum(n_out - 1) gaps. A token that never came takes the wait
    from the request's last token (or its due time) to the end of the run."""
    gaps: List[float] = []
    for r in reqs:
        gaps += np.diff(r.times).tolist()
        missing = r.n_out - max(1, len(r.times))
        if missing > 0:
            gaps += [end - (r.times[-1] if r.times else r.due)] * missing
    return gaps


def attainment(met: Sequence[bool]) -> float:
    """Share (percent) of requests that met both limits."""
    return 100.0 * sum(met) / len(met) if met else 0.0
