"""Reduction of a profiler trace to device busy time, per-program and
per-kernel device time, and the top operations.

`load_events` reads an `.xplane.pb` with `jax.profiler.ProfileData` (JAX
alone) into plain event tuples; everything else works on those tuples, so
the tests can run it on a small trimmed trace recorded on the chip
(`testdata/`). On a TPU the device plane is `/device:TPU:<n>`; its
"XLA Ops" line holds one event per operation that ran on the chip (a
Pallas kernel is one such operation, named after its kernel function),
and its "XLA Modules" line one event per program (`jit_<name>(...)`).
"""
from __future__ import annotations

import bisect
import gzip
import json
import pathlib
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def find_trace(root: pathlib.Path) -> Optional[pathlib.Path]:
    found = sorted(root.glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def short_name(name: str) -> str:
    """An op event on a TPU is named by its whole HLO instruction
    (`%fusion.3 = bf16[...] fusion(...), ...`); a program by
    `jit_<fn>(<fingerprint>)`. Keep `fusion.3` and `jit_<fn>`."""
    if name.startswith("%"):
        return name[1:].split(" = ", 1)[0]
    if name.startswith("jit_") and name.endswith(")"):
        return name.split("(", 1)[0]
    return name


def load_events(path: pathlib.Path) -> List[Event]:
    """Every event of every line of every plane, with start times on one
    clock (the trace's own, in ns) and short names."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out: List[Event] = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, short_name(e.name),
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def save_events(events: Iterable[Event], path: pathlib.Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([list(e) for e in events], f)


def read_events(path: pathlib.Path) -> List[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*e) for e in json.load(f)]


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events
                   if e.plane.startswith("/device:") and e.line == OPS_LINE})


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals, ns in, s out."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total * 1e-9


def gaps(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Idle gaps between merged busy intervals: (start_ns, length_ns)."""
    out, cur_e = [], None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            out.append((cur_e, s - cur_e))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


def reduce(events: List[Event]) -> Dict:
    """Per device plane: busy seconds (union of op intervals), seconds and
    calls by op name and by program name; averaged busy over planes."""
    planes = device_planes(events)
    per_plane = {}
    for p in planes:
        ops = [e for e in events if e.plane == p and e.line == OPS_LINE]
        mods = [e for e in events if e.plane == p and e.line == MODULES_LINE]
        iv = [(e.start_ns, e.start_ns + e.dur_ns) for e in ops]
        by_op: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
        for e in ops:
            by_op[e.name][0] += e.dur_ns * 1e-9
            by_op[e.name][1] += 1
        by_mod: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
        for e in mods:
            by_mod[e.name][0] += e.dur_ns * 1e-9
            by_mod[e.name][1] += 1
        per_plane[p] = {"busy_s": union_seconds(iv), "ops": dict(by_op),
                        "modules": dict(by_mod), "gaps": gaps(iv),
                        "ops_in": _attribute(ops, mods),
                        "span_ns": ((min(s for s, _ in iv),
                                     max(e for _, e in iv)) if iv else None)}
    busy = (sum(v["busy_s"] for v in per_plane.values()) / len(per_plane)
            if per_plane else 0.0)
    return {"planes": per_plane, "busy_s": busy}


def seconds_matching(red: Dict, kind: str, needle: str) -> Tuple[float, int]:
    """Summed device seconds and calls, over planes, of the ops
    (kind="ops") or programs (kind="modules") whose name contains
    `needle`."""
    s, n = 0.0, 0
    for plane in red["planes"].values():
        for name, (sec, calls) in plane[kind].items():
            if needle in name:
                s += sec
                n += calls
    return s, int(n)


CONTAINERS = ("while", "conditional", "call")


def top_ops(red: Dict, k: int = 10) -> List[List]:
    """The k operations that took most device time (summed over planes),
    named `<program>/<op>`. Control-flow ops (a layer loop's `while`) are
    left out: the ops inside them are events of their own."""
    tot: Dict[str, float] = defaultdict(float)
    for plane in red["planes"].values():
        for name, sec in plane["ops_in"].items():
            if name.split("/")[-1].split(".")[0] not in CONTAINERS:
                tot[name] += sec
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def _attribute(ops: List[Event], mods: List[Event]) -> Dict[str, float]:
    """Device seconds per `<program>/<op>`: each op goes to the program
    whose event contains its start on the same plane."""
    mods = sorted(mods, key=lambda e: e.start_ns)
    starts = [m.start_ns for m in mods]
    out: Dict[str, float] = defaultdict(float)
    for e in ops:
        i = bisect.bisect_right(starts, e.start_ns) - 1
        prog = (mods[i].name if i >= 0 and e.start_ns < mods[i].start_ns
                + mods[i].dur_ns else "?")
        out[f"{prog}/{e.name}"] += e.dur_ns * 1e-9
    return dict(out)


# host events that are a thread waiting, not working (the M_L worker's
# queue poll): they say nothing about why the device sat idle
WAITS = ("queue.py", "threading.py", " acquire")


def idle_gaps(red: Dict, events: List[Event], k: int = 10) -> List[List]:
    """The k longest idle gaps of the first device plane, each named by
    the host event that overlaps it most (the profiler's own host events:
    Python functions, jit dispatch, transfers), or "no host event"."""
    planes = list(red["planes"])
    if not planes:
        return []
    longest = sorted(red["planes"][planes[0]]["gaps"], key=lambda g: -g[1])[:k]
    host = [e for e in events if e.plane.startswith("/host:")
            and e.dur_ns > 0 and not any(w in e.name for w in WAITS)]
    out = []
    for start, length in longest:
        end = start + length
        best, best_ov = "no host event", 0.0
        for e in host:
            ov = min(end, e.start_ns + e.dur_ns) - max(start, e.start_ns)
            if ov > best_ov and e.dur_ns < 10 * length + 1e6:
                best, best_ov = e.name, ov
        out.append([best, length * 1e-9])
    return out
