"""host_idle_ms.lat (engine host loop): device idle time that the
engine's host loop holds the chip for, per decode step, in ms.

The idle gaps of the first device plane (between merged op intervals,
as device_idle.lat counts them) are cut to the union of the engine
thread's own spans in the trace (`engine.*` profiler annotations; left
out: `engine.iteration`, the parent of all of them, and `engine.wait`,
the sleep until the next arrival, which is time with no work), summed,
and divided by the calls of the decode-step program in the trace. A
program that emits no `engine.*` span reads nothing."""
import reading

SKIP = ("engine.iteration", "engine.wait")


def merged(intervals):
    """Union of [start, end) intervals as sorted disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap_ns(gaps, spans):
    """Length of the part of (start, length) gaps inside sorted disjoint
    [start, end) spans."""
    total, j = 0.0, 0
    for start, length in sorted(gaps):
        end = start + length
        while j < len(spans) and spans[j][1] <= start:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < end:
            total += min(end, spans[k][1]) - max(start, spans[k][0])
            k += 1
    return total


def read(run):
    if run.trace is None or not run.trace["planes"] or not run.events:
        return None
    import trace_reduce
    engine = [e for e in run.events if e.plane.startswith("/host:")
              and e.name.startswith("engine.")]
    _, steps = trace_reduce.seconds_matching(run.trace, "modules",
                                             reading.STEP_PROGRAM)
    if not engine or not steps:
        return None
    spans = merged((e.start_ns, e.start_ns + e.dur_ns) for e in engine
                   if e.name not in SKIP)
    first = next(iter(run.trace["planes"].values()))
    return 1e-6 * overlap_ns(first["gaps"], spans) / steps
