#!/usr/bin/env python3
"""Readings for setting a configuration's cells, many runs in one process
(one set-up), on the chip. The benchmark's own runs never run this.

    # program and float8 control readings over a dozen seeds
    python3 benchmarks/chip/study.py --runs ilm2x2.chat.rate \
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --seconds 51 --control 1
    # the gate faults: the program never, or always, defers
    python3 benchmarks/chip/study.py --runs ilm2x2.chat.rate \
        --seeds 1,2,3 --seconds 51 --fault never,always
    # a rate sweep (knee, capacity)
    python3 benchmarks/chip/study.py --seeds 1 --seconds 45 \
        --runs ilm2x2.chat.rate@0.6,ilm2x2.chat.rate@0.8,ilm2x2.chat.rate@5

`--runs` lists `<cell>` or `<cell>@<rate>` (the rate replaces the traffic
file's); every cell must share one configuration. `--fault` plants a
fault in the program's first edge for a set of runs each: `never` moves
its threshold to -inf, `always` to +inf; the comparison still holds the
runs to the calibrated one. Each run prints one JSON line: the seed,
cell, rate and fault, the cell's end-to-end metrics, the mean queue wait
of the requests due in the first, second and last third of the window (a
backlog that grows shows as the last above the first), requests
completed per second inside the window, the realized hard share, and,
without rates or with `--check 1`, the program's readings of the
comparison (and the control's, with `--control 1`).
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time
import types

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))


def thirds(reqs, measured, window):
    start, end = window
    third = (end - start) / 3
    waits = [[], [], []]
    for r, m in zip(reqs, measured):
        if m:
            k = min(int((r.arrival_time - start) / third), 2)
            w = r.t_admit - r.arrival_time if math.isfinite(r.t_admit) \
                else float("inf")
            waits[k].append(w)
    return [sum(w) / len(w) if w else None for w in waits]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default="",
                    help="comma list of never, always: planted gate faults")
    ap.add_argument("--check", type=int, default=0,
                    help="1: read the comparison (also with rates)")
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    import correct
    import harness
    import model
    import run
    import traffic as traffic_lib
    import window

    bench = run.load_bench(ROOT)
    bench_dir = ROOT / bench["paths"][0]
    runs = []
    for item in args.runs.split(","):
        name, _, rate = item.partition("@")
        runs.append((run.cell_of(bench, name), float(rate) if rate else None))
    configs = {c["config"] for c, _ in runs}
    if len(configs) != 1:
        raise SystemExit(f"--runs spans configurations {sorted(configs)}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] in configs)
    cfg = json.loads((ROOT / cfg_entry["file"]).read_text())
    archs, tiers = model.load_tiers(bench_dir, cfg, cfg_entry["file"])
    run.enable_cache(ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = [f for f in args.fault.split(",") if f] or [None]
    vocab = tiers[0].vocab_size
    sv = cfg["serving"]
    t0 = time.time()
    prog = harness.build(cfg, archs, tiers, cfg["weights_seed"])
    first = traffic_lib.build_schedule(bench_dir, runs[0][0]["traffic"],
                                       seeds[0], args.seconds, vocab,
                                       runs[0][1])
    cal = traffic_lib.calibration_prompts(first.mix, vocab)
    harness.calibrate(prog, cal, first.max_new, first.hard_share)
    harness.warm_up(prog, first, vocab)
    print(f"set-up {time.time() - t0:.1f} s", file=sys.stderr, flush=True)
    compiles = harness.Compiles()
    gate = correct.Gate(prog.taus[0], sv["margin"], sv["min_tokens"],
                        sv["early_exit"])
    planted = {None: gate.tau, "never": -math.inf, "always": math.inf}
    for fault in faults:
        for e in prog.engine.spec.edges:
            e.tau = planted[fault]
        for seed in seeds:
            for cell, rate in runs:
                sched = traffic_lib.build_schedule(
                    bench_dir, cell["traffic"], seed, args.seconds, vocab,
                    rate)
                n0 = compiles.n
                reqs, stats, _ = harness.serve(prog, sched, lambda t: None)
                measured = [s.measured for s in sched.specs]
                start, end = sched.window
                out = {"seed": seed, "fault": fault,
                       "cell": cell["name"],
                       "rate": rate or sched.traffic["rate_per_s"],
                       "compiles": compiles.n - n0,
                       "outcomes": window.outcome_counts(reqs, measured),
                       "queue_wait_thirds": thirds(reqs, measured,
                                                   sched.window),
                       "completed_per_s": sum(
                           1 for r in reqs if r.state == "done"
                           and start <= r.t_done < end) / (end - start),
                       "hard_share": sum(r.deferred for r, m in
                                         zip(reqs, measured) if m)
                       / max(sum(measured), 1),
                       "tier_served": stats.get("tier_served"),
                       "taus": prog.taus}
                ns = types.SimpleNamespace(reqs=reqs, measured=measured,
                                           window=sched.window, setup_s=0.0)
                for name in run.metric_names(bench, cell, "end_to_end"):
                    if name != "setup_s":
                        out[name] = run.load_reader(bench_dir, name)(ns)
                if args.check or rate is None:
                    checked = correct.collect(reqs, measured)
                    t_ref = time.time()
                    out["readings"] = correct.readings(
                        checked, prog.archs, prog.tiers, cfg["weights_seed"],
                        harness.DTYPES[cfg["dtype"]], gate,
                        control=bool(args.control))
                    out["reference_s"] = time.time() - t_ref
                    out["checked"] = len(checked)
                print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
