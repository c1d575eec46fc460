"""Shared readings for the per-layer metric readers: the engine's audit
log replayed into per-step slot positions, and the reduced trace.

The audit log is the program's own event stream (one JSON object per
line, `t` in seconds on the engine's clock): `admit` (rids, slots),
`prefill_done` (rid, slot), `step` (the slots one decode step ran),
`retire` (rid, slot), `large_complete` (one per row of an upper tier's
batch: tier, batch_id, n_real, pad_to).
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional, Tuple

STEP_PROGRAM = "jit_step_fn"


def audit_events(path) -> List[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def replay_steps(events: List[dict], prompt_len: Dict[int, int]
                 ) -> List[dict]:
    """Every decode step with its slots and, per slot, the position the
    step writes (it attends to that many positions plus one). A slot
    starts decoding at its prompt length once its prefill is done."""
    pos: Dict[int, int] = {}
    out = []
    for e in events:
        kind = e["event"]
        if kind == "prefill_done":
            pos[e["slot"]] = prompt_len[e["rid"]]
        elif kind == "resume":
            raise ValueError("preempted requests are not replayed")
        elif kind == "step":
            slots = list(e["slots"])
            out.append({"t": e["t"], "slots": slots,
                        "positions": [pos[s] for s in slots]})
            for s in slots:
                pos[s] += e.get("n", 1)
    return out


def steps_in(run, t0: float, t1: float) -> List[dict]:
    if run.audit is None:
        return []
    steps = run.cache.get("steps")
    if steps is None:
        plen = {r.rid: r.prompt_len for r in run.reqs}
        steps = run.cache["steps"] = replay_steps(run.audit, plen)
    return [s for s in steps if t0 <= s["t"] < t1]


def ml_batches(run, t0: float, t1: float) -> Dict[Tuple[int, int], dict]:
    """Upper-tier batches completed in [t0, t1), keyed (tier, batch_id)."""
    out = {}
    for e in run.audit or ():
        if e["event"] == "large_complete" and t0 <= e["t"] < t1:
            out[(e["tier"], e["batch_id"])] = e
    return out


def decode_step_ms(run) -> Optional[float]:
    if run.trace is None:
        return None
    import trace_reduce
    sec, calls = trace_reduce.seconds_matching(run.trace, "modules",
                                               STEP_PROGRAM)
    return 1e3 * sec / calls if calls else None


def device_idle_pct(run) -> Optional[float]:
    if run.trace is None or not run.trace["planes"]:
        return None
    t0, t1 = run.trace_window
    return 100.0 * (1.0 - run.trace["busy_s"] / (t1 - t0))
