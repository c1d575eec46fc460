"""paged_attn_roofline.lat (kernels): the least time the chip needs for
the paged GQA decode kernel's calls in the trace over their device time,
in percent. Tier 0's architecture module lists each decode step's kernel
calls with the bytes and FLOPs each needs (`paged_attn_calls`); a call's
least time is the larger of its bytes over peak bandwidth and its FLOPs
over peak FLOP/s, and the traced calls are priced at the mean over the
calls of the steps in the window. Every row's valid positions are taken
from the engine's audit log: each decode step event lists its slots, and
a slot's position follows from its request's prompt length and the steps
since its prefill."""
import reading

KERNEL = "paged_flash_decode_gqa"


def read(run):
    if run.trace is None or run.audit is None:
        return None
    import trace_reduce
    sec, calls = trace_reduce.seconds_matching(run.trace, "ops", KERNEL)
    steps = reading.steps_in(run, *run.trace_window)
    if not calls or not steps:
        return None
    arch, t = run.archs[0], run.tiers[0]
    bw, fl = run.peak["hbm_bytes_per_s"], run.peak["bf16_flops_per_s"]
    need, n = 0.0, 0
    for s in steps:
        for b, f in arch.paged_attn_calls(t, s["positions"],
                                          len(s["slots"])):
            need += max(b / bw, f / fl)
            n += 1
    if not n:
        return None
    return 100.0 * need / n * calls / sec
