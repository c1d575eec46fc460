"""step_mfu.lat (whole step): the model FLOPs of tier 0's paged decode
steps in the trace (every active row's token through the trunk and the
unembedding, and its attention over its valid positions, as tier 0's
architecture module counts them: `decode_step_flops`) over their device
time times the chip's peak bf16 FLOP/s, in percent. The rows and
positions of each step come from the engine's audit log, as for
paged_attn_roofline.lat; the step's device time is that of its program
in the trace, which holds the paged kernel's calls, so this share bounds
what the kernel's can show."""
import reading


def read(run):
    if run.trace is None or run.audit is None:
        return None
    import trace_reduce
    sec, calls = trace_reduce.seconds_matching(run.trace, "modules",
                                               reading.STEP_PROGRAM)
    steps = reading.steps_in(run, *run.trace_window)
    if not calls or not steps:
        return None
    arch, t = run.archs[0], run.tiers[0]
    per_step = sum(arch.decode_step_flops(t, s["positions"])
                   for s in steps) / len(steps)
    return 100.0 * per_step * calls / (sec * run.peak["bf16_flops_per_s"]
                                       * run.n_chips)
