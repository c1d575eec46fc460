"""Traffic generator: one general generator, driven by data files.

A traffic file (`traffic/<name>.json`) names a mix (`mixes/<mix>.json`:
length distributions, grid, hard share) and an arrival process (rate,
lead-in before the measured window, tail after it, and optionally one
prompt length for the tail). The generator turns
the pair, a seed and the window length into a fixed request schedule:

* sizes: prompt and output lengths are taken at the stratified quantiles
  (i + 0.5) / n of their log-normal laws, clipped and rounded up to the
  grid, and paired by a permutation fixed by the mix.
* tokens: the Zipf-Markov stream of `repro.data.synthetic.make_lm_stream`
  (copied below so that the yardstick cannot move with the program),
  drawn per (prompt, output) pair from the mix's own seed.
* arrivals: the gaps of a Poisson process of the given rate, at
  stratified exponential quantiles.

The run seed shuffles the requests and the gaps of each phase. Every
seed therefore serves the same requests, token for token, at the same
multiset of gaps: with the configuration's fixed weights the same ones
are deferred, so the work of a run does not depend on the seed. The
longest request (which fixes the engine's compiled shapes) never moves.

Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load_json(root: pathlib.Path, kind: str, name: str) -> dict:
    """`<root>/<kind>/<name>.json`, the harness's one lookup by name."""
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Spec:
    """One request of a schedule: prompt tokens, output budget, due time
    (seconds after the engine starts) and whether it is measured."""
    prompt: np.ndarray
    max_new: int
    arrival: float
    measured: bool


@dataclasses.dataclass
class Schedule:
    specs: List[Spec]
    window: tuple          # (start, end) seconds on the engine's clock
    max_new: int           # run-wide output cap (the engine's buffer width)
    hard_share: float
    mix: dict
    traffic: dict

    @property
    def max_len(self) -> int:
        return max(s.prompt.shape[0] + s.max_new for s in self.specs)


def seed_words(seed: int) -> tuple:
    """A run seed of any size as two 32-bit words (jax keys and numpy
    generators are both built from these, so seeds past 2**32 are fine)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def rng_for(seed: int, stream: int) -> np.random.Generator:
    lo, hi = seed_words(seed)
    return np.random.default_rng([lo, hi, stream])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(law: dict, n: int) -> np.ndarray:
    """n lengths at stratified quantiles of a log-normal law with the
    given median and sigma, clipped to [min, max] and rounded up to the
    law's grid (1 when absent)."""
    z = np.array([NormalDist().inv_cdf(float(q)) for q in _quantiles(n)])
    x = law["median"] * np.exp(law["sigma"] * z)
    x = np.clip(np.ceil(x), law["min"], law["max"])
    grid = int(law.get("grid", 1))
    x = np.ceil(x / grid) * grid
    return np.minimum(x, law["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """n inter-arrival gaps of a Poisson process at `rate`, at stratified
    quantiles of the exponential law."""
    return -np.log1p(-_quantiles(n)) / rate


def size_pairs(mix: dict, n: int) -> List[tuple]:
    """The mix's n (prompt, output) pairs, in a fixed order: outputs are
    paired with prompts by a permutation drawn from the mix's own seed,
    except that the longest prompt gets the longest output. So every
    schedule's longest request is (prompt max, output max) once the laws
    reach their clips, whatever the rate and window: the engine's
    compiled shapes, which are keyed on it, never change."""
    prompts = lognormal_lengths(mix["prompt"], n)
    outputs = lognormal_lengths(mix["output"], n)
    perm = np.random.default_rng(int(mix["seed"])).permutation(n)
    top = int(np.flatnonzero(perm == n - 1)[0])
    perm[top], perm[n - 1] = perm[n - 1], n - 1
    return [(int(p), int(o)) for p, o in zip(prompts, outputs[perm])]


def lm_stream(rng: np.random.Generator, n_seqs: int, seq_len: int,
              vocab: int) -> np.ndarray:
    """Zipf-Markov token stream: a 64-state machine with 8 branches that
    emits one of 512 tokens, mixed 15% with Zipf unigrams over the whole
    vocabulary. Copied from `repro.data.synthetic.make_lm_stream`, with
    the generator passed in."""
    V = vocab
    zipf = 1.0 / np.arange(1, V + 1)
    zipf /= zipf.sum()
    n_states = 64
    state_next = rng.integers(0, n_states, size=(n_states, 8))
    state_emit = (rng.permutation(V)[:n_states * 8].reshape(n_states, 8)
                  if V >= n_states * 8
                  else rng.integers(0, V, size=(n_states, 8)))
    out = np.empty((n_seqs, seq_len), np.int32)
    state = rng.integers(0, n_states, size=n_seqs)
    for t in range(seq_len):
        branch = rng.integers(0, 8, size=n_seqs)
        zipf_mask = rng.random(n_seqs) < 0.15
        tok = state_emit[state, branch]
        tok[zipf_mask] = rng.choice(V, size=int(zipf_mask.sum()), p=zipf)
        out[:, t] = tok
        state = state_next[state, branch]
    return out


def prompts_for(lengths: List[int], vocab: int, rng) -> List[np.ndarray]:
    """One token vector per length, cut from one Zipf-Markov stream."""
    if not lengths:
        return []
    base = lm_stream(rng, len(lengths), max(lengths), vocab)
    return [base[i, :n].copy() for i, n in enumerate(lengths)]


def build_schedule(root: pathlib.Path, traffic_name: str, seed: int,
                   seconds: float, vocab: int,
                   rate: Optional[float] = None) -> Schedule:
    """The request schedule of one run of `traffic_name` (`rate` replaces
    the file's, for sweeps)."""
    traffic = load_json(root, "traffic", traffic_name)
    mix = load_json(root, "mixes", traffic["mix"])
    rate = float(rate or traffic["rate_per_s"])
    lead = float(traffic.get("lead_s", 0.0))
    tail = float(traffic.get("tail_s", 0.0))
    start, end = lead, lead + float(seconds)
    rng = rng_for(seed, 1)
    mix_seed = int(mix["seed"])
    tail_len = traffic.get("tail_prompt_len")
    # three phases, each with its own fixed multiset of sizes and gaps:
    # the lead-in fills the system, the window is measured, the tail keeps
    # the load on while the window's requests finish (with the mix's
    # prompt lengths, or one length when the traffic file fixes it)
    phases = [(0.0, lead, False, None), (start, end, True, None),
              (end, end + tail, False, tail_len)]
    specs = []
    for phase, (t0, t1, meas, fixed_len) in enumerate(phases):
        n = int(math.ceil(rate * (t1 - t0)))
        if n == 0:
            continue
        pairs = size_pairs(mix, n)
        prompts = prompts_for([int(fixed_len or p) for p, _ in pairs], vocab,
                              np.random.default_rng([mix_seed, phase]))
        order = rng.permutation(n)
        gaps = rng.permutation(exponential_gaps(rate, n))
        # gaps scaled to span the phase: the first request is due at its
        # start and the last one before its end
        at = t0 + (t1 - t0) * (np.cumsum(gaps) - gaps[0]) / gaps.sum()
        for k, i in enumerate(order):
            specs.append(Spec(prompts[i], pairs[i][1], float(at[k]), meas))
    return Schedule(specs, (start, end), int(mix["output"]["max"]),
                    float(mix["hard_share"]), mix, traffic)


def calibration_prompts(mix: dict, vocab: int) -> np.ndarray:
    """A separate draw of the mix's tokens for calibrating the deferral
    edges, at one length (the engine calibrates on a uniform batch); fixed
    by the mix, like the served tokens."""
    cal = mix["calibration"]
    return lm_stream(np.random.default_rng([int(mix["seed"]), 3]),
                     int(cal["n"]), int(cal["prompt_len"]), vocab)


def warmup_lengths(slots: int, chunk: int, grid_max: int) -> List[int]:
    """Prompt lengths of one all-at-once warm-up batch whose same-offset
    prefill groups pass through every power-of-two dispatch width up to
    next_pow2(slots): `slots` prompts share chunk offset 0, and at each
    later offset the number still prefilling is the next lower power of
    two, down to one prompt of the longest length."""
    counts = [slots]
    while counts[-1] > 1:
        counts.append(1 << ((counts[-1] - 1).bit_length() - 1))
    lengths: List[int] = []
    for j in range(len(counts) - 1):
        lengths += [min((j + 1) * chunk, grid_max)] * (counts[j]
                                                       - counts[j + 1])
    return lengths + [grid_max]


def describe(schedule: Schedule) -> Dict[str, float]:
    measured = [s for s in schedule.specs if s.measured]
    return {"requests": len(schedule.specs), "measured": len(measured),
            "window_start_s": schedule.window[0],
            "window_end_s": schedule.window[1]}
