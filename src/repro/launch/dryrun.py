import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import (device count locks on first init).

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the single-pod (16,16) and multi-pod (2,16,16) production meshes, print
memory/cost analyses, and emit roofline terms.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-1.8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all            # single-pod, 40 combos
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
Results are appended as JSON lines to benchmarks/results/dryrun.jsonl.
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Optional

import jax

from repro.configs import SHAPES, get_config, list_configs
from repro.launch import roofline as rf
from repro.launch.mesh import make_production_mesh, make_context
from repro.launch.specs import (adapt_for_shape, batch_specs, cache_specs,
                                decode_specs, model_state_specs)
from repro.launch.steps import make_prefill, make_serve_step, make_train_step


def _lower_for(cfg, shape, mesh, ctx, rules=None, opt_rules=None):
    """Build + lower the appropriate step for `shape.kind`."""
    with jax.default_device(jax.devices("cpu")[0]):
        if shape.kind == "train":
            params, opt = model_state_specs(cfg, mesh, with_opt=True,
                                            rules=rules,
                                            opt_rules=opt_rules)
            batch = batch_specs(cfg, shape, mesh, rules=rules)
            step = make_train_step(cfg, ctx,
                                   microbatches=cfg.microbatches)
            return jax.jit(step, donate_argnums=(0, 1)).lower(
                params, opt, batch)
        if shape.kind == "prefill":
            params, _ = model_state_specs(cfg, mesh, with_opt=False,
                                          rules=rules)
            cache = cache_specs(cfg, shape, mesh, rules=rules)
            batch = batch_specs(cfg, shape, mesh, rules=rules)
            fn = make_prefill(cfg, ctx)
            return jax.jit(fn, donate_argnums=(1,)).lower(
                params, cache, batch)
        params, _ = model_state_specs(cfg, mesh, with_opt=False, rules=rules)
        cache = cache_specs(cfg, shape, mesh, rules=rules)
        token, position = decode_specs(cfg, shape, mesh, rules=rules)
        fn = make_serve_step(cfg, ctx)
        return jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, token, position)


def _depth_points(cfg):
    """Two shallow depths whose UNROLLED costs extrapolate linearly to L.
    (XLA's HloCostAnalysis counts a scan body once, so full-depth
    cost_analysis under-reports by ~L; we unroll shallow variants and use
    f(L) ≈ f(d1) + (L-d1)/(d2-d1) * (f(d2)-f(d1)).)"""
    if cfg.family == "moe":
        return 2, 3            # 1 dense + 1/2 moe layers
    if cfg.family == "hybrid":
        return cfg.shared_attn_every, 2 * cfg.shared_attn_every
    return 1, 2


def _shallow_cfg(cfg, d):
    kw = dict(n_layers=d, scan_layers=False)
    if cfg.encoder is not None:
        kw["encoder"] = dataclasses.replace(cfg.encoder, n_layers=d)
    return cfg.replace(**kw)


def _measured_costs(cfg, shape, mesh, ctx, rules=None, opt_rules=None):
    """(flops, bytes, coll_breakdown) per device from one compile."""
    lowered = _lower_for(cfg, shape, mesh, ctx, rules=rules,
                         opt_rules=opt_rules)
    compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    coll = rf.collective_bytes(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), coll)


def extrapolated_costs(cfg, shape, mesh, ctx, rules=None, opt_rules=None):
    """Depth-extrapolated per-device (flops, bytes, coll_breakdown)."""
    d1, d2 = _depth_points(cfg)
    f1 = _measured_costs(_shallow_cfg(cfg, d1), shape, mesh, ctx, rules,
                         opt_rules)
    f2 = _measured_costs(_shallow_cfg(cfg, d2), shape, mesh, ctx, rules,
                         opt_rules)
    L = cfg.n_layers
    k = (L - d1) / (d2 - d1)
    flops = f1[0] + k * (f2[0] - f1[0])
    byts = f1[1] + k * (f2[1] - f1[1])
    coll = {key: f1[2][key] + k * (f2[2][key] - f1[2][key]) for key in f1[2]}
    return flops, byts, coll


def lower_combo(arch: str, shape_name: str, multi_pod: bool,
                remat: str = "none", verbose: bool = True,
                skip_extrapolation: bool = False,
                rule_overrides: Optional[dict] = None,
                label: Optional[str] = None,
                cfg_overrides: Optional[dict] = None,
                opt_rule_overrides: Optional[dict] = None):
    """Lower + compile one (arch, shape, mesh). Returns result dict.

    rule_overrides: logical-axis -> mesh-axes overrides (hillclimb knob).
    cfg_overrides:  ModelConfig.replace(**...) applied after shape adapt.
    """
    from repro.sharding import rules_dict
    shape = SHAPES[shape_name]
    cfg = adapt_for_shape(get_config(arch), shape)
    if remat != "none":
        cfg = cfg.replace(remat=remat)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    rules = rules_dict(rule_overrides) if rule_overrides else None
    opt_rules = (rules_dict({**(rule_overrides or {}), **opt_rule_overrides})
                 if opt_rule_overrides else None)
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = make_context(mesh)
    if rules is not None:
        ctx = dataclasses.replace(ctx, rules=rules)
    n_dev = mesh.devices.size
    mesh_name = "2x16x16" if multi_pod else "16x16"

    t0 = time.time()
    lowered = _lower_for(cfg, shape, mesh, ctx, rules=rules,
                         opt_rules=opt_rules)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    memstats = compiled.memory_analysis()
    hlo = compiled.as_text()
    if skip_extrapolation:
        flops, byts = (float(cost.get("flops", 0.0)),
                       float(cost.get("bytes accessed", 0.0)))
        coll_bd = rf.collective_bytes(hlo)
    else:
        flops, byts, coll_bd = extrapolated_costs(cfg, shape, mesh, ctx,
                                                  rules, opt_rules)
    coll = coll_bd["total"]
    model_flops = rf.analytic_model_flops(cfg, shape)
    report = rf.make_report(
        arch, shape_name, mesh_name, n_dev,
        {"flops": flops, "bytes accessed": byts}, "", model_flops, memstats)
    report.collective_bytes_per_device = coll
    report.collective_s = coll / rf.ICI_BW
    report.collectives = coll_bd
    report.dominant = max(
        (("compute", report.compute_s), ("memory", report.memory_s),
         ("collective", report.collective_s)), key=lambda kv: kv[1])[0]
    result = dataclasses.asdict(report)
    result.update({
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        "remat": remat, "label": label,
        "rule_overrides": rule_overrides, "cfg_overrides": cfg_overrides,
        "scan_body_flops": float(cost.get("flops", 0.0)),
        "arg_bytes": int(memstats.argument_size_in_bytes),
        "temp_bytes": int(memstats.temp_size_in_bytes),
        "out_bytes": int(memstats.output_size_in_bytes),
    })
    if verbose:
        print(f"== {arch} × {shape_name} × {mesh_name} ==")
        print("memory_analysis:", memstats)
        print("cost_analysis flops/device:", cost.get("flops"),
              "bytes/device:", cost.get("bytes accessed"))
        print(f"roofline: compute={report.compute_s:.4g}s "
              f"memory={report.memory_s:.4g}s "
              f"collective={report.collective_s:.4g}s "
              f"-> dominant={report.dominant}; "
              f"useful-flops ratio={report.useful_flops_ratio:.3g}; "
              f"fits_hbm={report.fits_hbm}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--remat", default="none")
    ap.add_argument("--skip-extrapolation", action="store_true")
    ap.add_argument("--out", default="benchmarks/results/dryrun.jsonl")
    args = ap.parse_args()

    combos = []
    archs = [get_config(a).name for a in list_configs()] \
        if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    for a in archs:
        for s in shapes:
            combos.append((a, s))

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    failures = []
    with open(args.out, "a") as f:
        for arch, shape in combos:
            try:
                res = lower_combo(arch, shape, args.multi_pod,
                                  remat=args.remat,
                                  skip_extrapolation=args.skip_extrapolation)
                f.write(json.dumps(res) + "\n")
                f.flush()
            except Exception as e:
                traceback.print_exc()
                failures.append((arch, shape, repr(e)[:200]))
    if failures:
        print("FAILURES:")
        for fa in failures:
            print(" ", fa)
        raise SystemExit(1)
    print(f"all {len(combos)} combos lowered+compiled OK "
          f"({'multi-pod 2x16x16' if args.multi_pod else 'single-pod 16x16'})")


if __name__ == "__main__":
    main()
