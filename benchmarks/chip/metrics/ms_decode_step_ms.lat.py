"""ms_decode_step_ms.lat (model step): device time of the engine's paged
decode-step program in the trace over its number of calls."""
import reading


def read(run):
    return reading.decode_step_ms(run)
