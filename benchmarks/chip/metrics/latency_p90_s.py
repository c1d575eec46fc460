"""latency_p90_s: 90th percentile over every request due in the window of
t_done - arrival; a request that did not finish counts as infinite."""
import window


def read(run):
    return window.percentile(window.latencies(run.reqs, run.measured), 90)
