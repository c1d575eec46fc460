"""The comparison that decides `correct`.

After the window has closed, every request due in the window that
finished is checked against the plain fp32 reference of each tier that
read it (its architecture module's `reference_readings`), run once the
program's device state has been freed. Two numbers are read; each is
compared with its limit where the configuration file gives one:

* served_gap: the widest gap, over every served token, by which the
  served token's reference logit lies below the reference's best logit
  at that position, read on the tier that served the request. Greedy
  decoding serves the best token, so a sound run reads only the rounding
  of near-ties.
* gate_miss: how far, at worst, the reference's eq.-8 confidence lies on
  the other side of the first edge's threshold from a decision the
  program took. Tier 0 decides after each decode step from the running
  mean of the negative entropy of its next-token distributions (`Gate`):
  a request is evicted up the ladder at the first step, from
  `min_tokens` on, at which that mean falls below tau - margin (with
  early exit), or deferred when its final mean is below tau. The
  reference's running means over the same tokens are held to every one
  of those decisions: each step the request stayed, and the step it left
  or finished. A sound run reads no more than the rounding of the
  confidence; one that defers too little or too much reads the distance
  of the reference's confidence from tau. The upper edges are not
  checked: their confidence is taken over tokens past the request's
  budget, which the program does not return.

The control (`quant="fp8"`) is the reference itself computed in float8:
its served_gap is the gap of the token it puts first at each position of
the same sequences.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

import model


@dataclasses.dataclass
class Gate:
    """The first edge as the program ran it."""
    tau: float
    margin: float
    min_tokens: int
    early_exit: bool


@dataclasses.dataclass
class Checked:
    rid: int
    tier: int
    prompt: np.ndarray
    tokens: np.ndarray          # served tokens
    small_tokens: np.ndarray    # tokens tier 0 decoded before it let go
    deferred: bool
    early: bool                 # left tier 0 before its budget
    gated: bool                 # decided by the confidence gate


def collect(reqs, measured: Sequence[bool]) -> List[Checked]:
    """Every finished request due in the window."""
    out = []
    for r, m in zip(reqs, measured):
        if not (m and r.state == "done" and len(r.tokens) > 0):
            continue
        small = np.asarray(r.small_tokens if r.small_tokens is not None
                           else [], np.int32)
        out.append(Checked(
            int(r.rid), int(r.tier), np.asarray(r.prompt, np.int32),
            np.asarray(r.tokens, np.int32), small, bool(r.deferred),
            bool(r.early_exited), r.deferred_reason is None))
    return out


def gate_miss(nent: np.ndarray, c: Checked, gate: Gate) -> float:
    """The reference's worst margin against the program's gate decisions
    for one request, from the negative entropy `nent` at each of its tier-0
    tokens (positive: the reference would have decided otherwise)."""
    if not c.gated or nent.shape[0] == 0:
        return -np.inf
    n_tok = nent.shape[0]
    mean = np.cumsum(nent.astype(np.float64)) / np.arange(1, n_tok + 1)
    early = gate.tau - gate.margin
    miss = [-np.inf]
    if gate.early_exit and n_tok > gate.min_tokens:
        # it stayed after each step from min_tokens to the one before last
        miss.append(float(np.max(early - mean[gate.min_tokens - 1:-1])))
    last = float(mean[-1])
    if not c.deferred:
        miss.append(gate.tau - last)
    elif c.early:
        miss.append(last - early)
    else:
        miss.append(last - gate.tau)
    return max(miss)


def readings(checked: List[Checked], archs: list, tiers: list,
             weights_seed: int, served_dtype, gate: Gate,
             control: bool = False) -> Dict[str, Dict[str, float]]:
    """{"program": {"served_gap": .., "gate_miss": ..}} and, with
    `control`, {"control": {"served_gap": ..}}.

    Tier 0 reads every request over the tokens it decoded (for a request it
    kept, the served ones: a served token that differs from what it
    decoded shows in served_gap); each upper tier reads the requests it
    served, over their served tokens."""
    quants = (None, "fp8") if control else (None,)
    gap = {q: 0.0 for q in quants}
    miss = -np.inf
    for k, (arch, t) in enumerate(zip(archs, tiers)):
        if k == 0:
            rows = [(c, c.tokens if c.tier == 0 else c.small_tokens)
                    for c in checked]
            rows = [(c, toks) for c, toks in rows if toks.shape[0] > 0]
        else:
            rows = [(c, c.tokens) for c in checked if c.tier == k]
        if not rows:
            continue
        res = arch.reference_readings(
            t, model.tier_key(weights_seed, k),
            [model.Sequence_(c.prompt, toks) for c, toks in rows],
            served_dtype, quants)
        fp32 = res[0]
        for i, (c, _) in enumerate(rows):
            if k == 0:
                miss = max(miss, gate_miss(fp32["nent"][i], c, gate))
            if c.tier != k:
                continue
            for q, r in zip(quants, res):
                other = fp32["chosen"][i] if q is None else \
                    r["ref_of_argmax"][i]
                gap[q] = max(gap[q], float(np.max(fp32["best"][i] - other)))
    # -1 when no request met the gate, as a reading JSON can carry
    out = {"program": {"served_gap": gap[None],
                       "gate_miss": max(float(miss), -1.0)}}
    if control:
        out["control"] = {"served_gap": gap["fp8"]}
    return out


def verdict(values: Dict[str, float], limits: Dict[str, float],
            failed: int, checked: int) -> bool:
    """Correct: every due request came back, something was compared, and
    every compared number is within its limit."""
    return (failed == 0 and checked > 0
            and all(values[k] <= limits[k] for k in limits))


def check_lines(values: Dict[str, float], limits: Dict[str, float],
                extra: Optional[Dict[str, float]] = None) -> Dict[str, dict]:
    out = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    for k, v in (extra or {}).items():
        out[k] = v
    return out
