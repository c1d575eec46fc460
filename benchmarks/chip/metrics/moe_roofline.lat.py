"""moe_roofline.lat (kernels): the least time the chip needs for the
held-expert grouped-matmul kernel's calls in the trace (`moe_gmm_decode`)
over their device time, in percent. Each decode step's counters come from
the engine's audit log (`moe` events: per layer, the held experts that
took a token and the assignments they took, pulled with the step's own
sync); tier 0's architecture module prices each layer's call from them
(`moe_calls`: bytes and FLOPs). A call's least time is the larger of its
bytes over peak bandwidth and its FLOPs over peak FLOP/s. Every layer
launches the kernel: a layer whose held experts took no token needs no
expert weights and is counted at what it needs, its activations only
(the kernel still fetches one block there, which the share reads as
waste). The traced calls are priced at the mean over the window's calls.
None where the program reports no expert counters or the trace holds no
such call."""
import reading  # noqa: F401  (the readers share its import path)

KERNEL = "moe_gmm_decode"


def read(run):
    if run.trace is None or run.audit is None:
        return None
    arch, t = run.archs[0], run.tiers[0]
    if not hasattr(arch, "moe_calls"):
        return None
    import trace_reduce
    sec, calls = trace_reduce.seconds_matching(run.trace, "ops", KERNEL)
    t0, t1 = run.trace_window
    steps = [e for e in run.audit
             if e["event"] == "moe" and t0 <= e["t"] < t1]
    if not calls or not steps:
        return None
    bw, fl = run.peak["hbm_bytes_per_s"], run.peak["bf16_flops_per_s"]
    need, n = 0.0, 0
    for e in steps:
        for b, f in arch.moe_calls(t, e["hit"], e["assign"]):
            need += max(b / bw, f / fl)
            n += 1
    return 100.0 * need / n * calls / sec
