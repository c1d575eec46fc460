"""device_idle.lat (device): share of the traced window in which no
operation ran on the chip, in percent."""
import reading


def read(run):
    return reading.device_idle_pct(run)
