#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
`BENCHMARK.json`, its configuration file (tiers, widths, serving
settings, weights seed, limits), each tier's architecture module
(`archs/<arch>.py`: weights, the program's layout, the reference, the
counts), its traffic file (`traffic/<name>.json`, naming a mix in
`mixes/`), and one reader per metric (`metrics/<name>.py`). The run
builds the cascade from the configuration's weights seed, orders the
mix's fixed requests by the run seed (`traffic.py`), warms up every
shape its traffic uses,
serves the schedule through `ContinuousCascadeEngine.run`, reads the
metrics (with `--trace 1` the per-layer ones, from a profiler trace of
the middle of the window and the engine's audit log), checks the served
tokens and the deferral decisions of every finished request due in the
window against the fp32 reference (`correct.py`), and prints one JSON
line last on stdout. It exits non-zero with no result line when JAX finds no TPU or
fewer chips than the cell asks for.

`--control 1` (not used by the benchmark's own runs) also computes the
float8 control's readings on the same requests.
"""
from __future__ import annotations

import os
import time

T_MODULE = time.time()

import argparse                          # noqa: E402
import importlib.util                    # noqa: E402
import json                              # noqa: E402
import pathlib                           # noqa: E402
import shutil                            # noqa: E402
import sys                               # noqa: E402
import types                             # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

TRACE_SECONDS = 4.0


def process_start() -> float:
    """Wall-clock time this process started (Linux), else module import."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(float(x.split()[1]) for x in f
                         if x.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return T_MODULE


def load_bench(root: pathlib.Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_of(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"no workload named {workload!r} in BENCHMARK.json")


def metric_names(bench: dict, cell: dict, kind: str) -> list:
    """The cell's metrics of one kind: those listing it, or listing no
    cells and moving (or being) an end-to-end metric the cell reports."""
    e2e = [m["name"] for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if kind == "end_to_end":
        return e2e
    return [m["name"] for m in bench["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])
            and m["moves"] in e2e]


def load_reader(bench_dir: pathlib.Path, name: str):
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_cache(root: pathlib.Path) -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(root: pathlib.Path, workload: str, seed: int, seconds: float,
             trace: bool, control: bool = False, devices=None,
             t_start: float = None, log=print) -> dict:
    """One run of one cell; returns the result object."""
    import jax

    import correct
    import harness
    import model
    import trace_reduce
    import traffic as traffic_lib
    import window

    t_start = process_start() if t_start is None else t_start
    bench = load_bench(root)
    cell = cell_of(bench, workload)
    bench_dir = root / bench["paths"][0]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = json.loads((root / cfg_entry["file"]).read_text())
    archs, tiers = model.load_tiers(bench_dir, cfg, cfg_entry["file"])
    devices = devices or jax.devices()[:cell["chips"]]
    peaks = json.loads((bench_dir / "peaks.json").read_text())
    kind = devices[0].device_kind
    if devices[0].platform == "tpu" and kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    peak = peaks.get(kind)
    enable_cache(root)
    compiles = harness.Compiles()

    vocab = tiers[0].vocab_size
    schedule = traffic_lib.build_schedule(bench_dir, cell["traffic"], seed,
                                          seconds, vocab)
    log(f"schedule: {traffic_lib.describe(schedule)}, max_len "
        f"{schedule.max_len}")
    before = time.time() - t_start
    t0 = time.perf_counter()
    prog = harness.build(cfg, archs, tiers, cfg["weights_seed"])
    for t in prog.tiers:
        log(f"tier {t.name}: {t.n_params / 1e9:.3f} B params")
    t1 = time.perf_counter()
    cal = traffic_lib.calibration_prompts(schedule.mix, vocab)
    taus = harness.calibrate(prog, cal, schedule.max_new,
                             schedule.hard_share)
    log(f"taus {taus} for hard share {schedule.hard_share}")
    t2 = time.perf_counter()
    warm = harness.warm_up(prog, schedule, vocab)
    log(f"set-up parts: process start to build {before:.2f} s, "
        f"weights {t1 - t0:.2f} s, calibration {t2 - t1:.2f} s, warm-up of "
        f"the engine {warm['engine']:.2f} s and of the upper tiers "
        f"{warm['upper']:.2f} s; {compiles.n} compiles "
        f"{compiles.seconds:.2f} s")

    out_dir = root / "benchmarks" / "results" / workload
    trace_dir = out_dir / "trace"
    audit = None
    tw = None
    if trace:
        shutil.rmtree(out_dir, ignore_errors=True)
        out_dir.mkdir(parents=True)
        audit = str(out_dir / "audit.jsonl")
        mid = sum(schedule.window) / 2
        tw = harness.TraceWindow(trace_dir, mid - TRACE_SECONDS / 2,
                                 mid + TRACE_SECONDS / 2)
    clock = {}

    def on_start(t_call):
        clock["setup_s"] = time.time() - t_start
        clock["compiles"] = compiles.n
        if tw is not None:
            tw.arm(t_call)

    reqs, stats, t_call = harness.serve(prog, schedule, on_start, audit)
    t_end = time.perf_counter() - t_call
    if tw is not None:
        tw.join()
    measured = [s.measured for s in schedule.specs]
    counts = window.outcome_counts(reqs, measured)
    n_compiles_run = compiles.n - clock["compiles"]
    peak_bytes = max(int(d.memory_stats().get("peak_bytes_in_use", 0))
                     for d in devices) if devices[0].platform == "tpu" else 0
    n_meas = sum(measured)
    deferred = sum(1 for r, m in zip(reqs, measured) if m and r.deferred)
    log(f"served: {counts}, {n_meas} due in the window, realized hard share "
        f"{deferred / max(n_meas, 1):.4f}, tier_served "
        f"{stats.get('tier_served')}, compiles during the run "
        f"{n_compiles_run}, run {t_end:.1f} s, peak bytes {peak_bytes} of "
        f"{devices[0].memory_stats().get('bytes_limit') if peak_bytes else 0}")

    run = types.SimpleNamespace(
        reqs=reqs, measured=measured, window=schedule.window, stats=stats,
        schedule=schedule, archs=prog.archs, tiers=prog.tiers, peak=peak,
        seconds=seconds, n_chips=len(devices), setup_s=clock["setup_s"],
        trace=None, trace_window=None, events=None, audit=None, cache={})
    result = {"correct": False, "attempted": counts["attempted"],
              "failed": counts["failed"], "metrics": {},
              "device": {"platform": devices[0].platform, "kind": kind,
                         "count": len(devices),
                         "memory_peak_bytes": peak_bytes}}
    kind_m = "per_layer" if trace else "end_to_end"
    if trace:
        import reading
        path = trace_reduce.find_trace(trace_dir)
        events = trace_reduce.load_events(path) if path else []
        trace_reduce.save_events(
            [e for e in events if e.plane.startswith("/device:")
             or e.dur_ns >= 5e4], out_dir / "events.json.gz")
        red = trace_reduce.reduce(events)
        run.trace, run.events = red, events
        run.trace_window = (tw.t_start, tw.t_stop)
        run.audit = reading.audit_events(audit)
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = tw.t_stop - tw.t_start
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(red),
            "idle_gaps": trace_reduce.idle_gaps(red, events)}
        log(f"trace {path}: busy {red['busy_s']:.4f} s of "
            f"{tw.t_stop - tw.t_start:.4f} s")
    units = {m["name"]: m["unit"] for m in bench[kind_m]}
    for name in metric_names(bench, cell, kind_m):
        value = load_reader(bench_dir, name)(run)
        if value is not None:
            result["metrics"][name] = {"value": value, "unit": units[name]}

    # correctness, after the window, on a chip that no longer holds the
    # program
    checked = correct.collect(reqs, measured)
    # the first edge as configured and calibrated, not as the engine holds
    # it: a program that moved its own threshold is held to this one
    sv = cfg["serving"]
    gate = correct.Gate(prog.taus[0], sv["margin"], sv["min_tokens"],
                        sv["early_exit"])
    del reqs, run
    harness.free(prog)
    t_ref = time.perf_counter()
    got = correct.readings(checked, prog.archs, prog.tiers,
                           cfg["weights_seed"], harness.DTYPES[cfg["dtype"]],
                           gate, control=control)
    log(f"reference over {len(checked)} requests "
        f"({sum(len(c.tokens) for c in checked)} served tokens, tiers "
        f"{sorted({c.tier for c in checked})}) in "
        f"{time.perf_counter() - t_ref:.1f} s")
    limits = cfg["correct"]["limits"]
    values = got["program"]
    result["correct"] = correct.verdict(values, limits, counts["failed"],
                                        len(checked))
    extra = {}
    if control:
        extra = {f"control_{k}": {"value": v, "limit": limits[k]}
                 for k, v in got["control"].items()}
    result["checks"] = correct.check_lines(values, limits, extra)
    result["checks"]["failed"] = {"value": counts["failed"], "limit": 0}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = process_start()
    sys.path.insert(0, str(ROOT / "src"))
    bench = load_bench(ROOT)
    cell = cell_of(bench, args.workload)
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"no accelerator: {e}", file=sys.stderr)
        return 2
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"cell asks for {cell['chips']} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), bool(args.control),
                      devices[:cell["chips"]], t_start, log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
