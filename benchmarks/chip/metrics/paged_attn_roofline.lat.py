"""paged_attn_roofline.lat (kernels): the least time the chip needs for
the paged GQA decode kernel's calls in the trace over their device time,
in percent. The least time of a call is the larger of its needed bytes
over peak bandwidth and its FLOPs over peak FLOP/s (costs.paged_attn_*),
with every row's valid positions taken from the engine's audit log: each
decode step event lists its slots, and a slot's position follows from
its request's prompt length and the steps since its prefill. Memory
bound at these shapes: bytes/bandwidth is the larger term."""
import costs
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
    t = run.tiers[0]
    bw, fl = run.peak["hbm_bytes_per_s"], run.peak["bf16_flops_per_s"]
    need = 0.0
    for s in steps:
        kv = sum(p + 1 for p in s["positions"])
        need += max(costs.paged_attn_bytes(kv, len(s["slots"]), t) / bw,
                    costs.paged_attn_flops(kv, t) / fl)
    per_call = need / len(steps)       # one call per layer per step
    return 100.0 * per_call * calls / sec
