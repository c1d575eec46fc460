"""setup_s: seconds from process start to the first due request
(weights, calibration, warm-up of the cell's own shapes)."""


def read(run):
    return run.setup_s
