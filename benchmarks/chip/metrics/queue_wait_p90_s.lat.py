"""queue_wait_p90_s.lat (scheduler): 90th percentile over the requests
due in the window of t_admit - arrival; never admitted counts as
infinite."""
import math

import window


def read(run):
    waits = [r.t_admit - r.arrival_time if math.isfinite(r.t_admit)
             else window.INF
             for r, m in zip(run.reqs, run.measured) if m]
    return window.percentile(waits, 90) if waits else None
