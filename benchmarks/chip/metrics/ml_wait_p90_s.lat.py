"""ml_wait_p90_s.lat (M_L tier): 90th percentile over the deferred
requests due in the window of t_done - t_retire (from leaving tier 0 to
the final tokens); a deferred request that did not finish counts as
infinite."""
import math

import window


def read(run):
    waits = [r.t_done - r.t_retire if r.state == "done"
             and math.isfinite(r.t_done) else window.INF
             for r, m in zip(run.reqs, run.measured) if m and r.deferred]
    return window.percentile(waits, 90) if waits else None
