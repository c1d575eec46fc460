"""End-to-end arithmetic over the requests of one run.

Copied in spirit from `repro.serving.telemetry.ServingTelemetry.summary`
(latency from each request's own timestamps), with two faults of that
summary as a yardstick fixed: a request that was due and did not finish
counts as an infinite latency instead of being dropped from the
percentiles.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

INF = float("inf")


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank q-th percentile (q in [0, 100]): the smallest value
    with at least q% of the sample at or below it. Infinite entries (a
    request that never finished) sort last; NaN is an error."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of an empty sample")
    if np.isnan(v).any():
        raise ValueError("NaN in a latency sample")
    k = max(int(math.ceil(q / 100.0 * v.size)), 1)
    return float(v[k - 1])


def latencies(reqs, measured: Sequence[bool]) -> List[float]:
    """t_done - arrival of every measured request; inf if it is not done."""
    out = []
    for r, m in zip(reqs, measured):
        if not m:
            continue
        done = r.state == "done" and math.isfinite(r.t_done)
        out.append(r.t_done - r.arrival_time if done else INF)
    return out


def outcome_counts(reqs, measured: Sequence[bool]) -> Dict[str, int]:
    """attempted: every request due in the window (each is offered and
    none is shed); failed: those that did not end with tokens."""
    att = fail = 0
    for r, m in zip(reqs, measured):
        if not m:
            continue
        att += 1
        if r.state != "done" or r.tokens is None:
            fail += 1
    return {"attempted": att, "failed": fail}
