"""A tiny cell for the CPU tests: the same files and layout as the real
benchmark (BENCHMARK.json, a configuration, a mix, a traffic file, the
architecture modules and the metric readers), at widths a CPU runs in
seconds."""
from __future__ import annotations

import json
import pathlib
import shutil

HERE = pathlib.Path(__file__).resolve().parent

TIER = {"arch": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
        "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 512,
        "rope_theta": 10000.0, "norm_eps": 1e-5}
CONFIG = {
    "name": "tiny.x2",
    "source": "https://arxiv.org/abs/2403.17297",
    "tiers": [dict(TIER, name="tiny-small"),
              dict(TIER, name="tiny-large", d_model=96, d_ff=192)],
    "dtype": "bfloat16",
    "weights_seed": 1,
    "serving": {"slots": 4, "n_blocks": 96, "block_size": 8,
                "prefill_chunk": 32, "paged_kernel": False,
                "prefix_sharing": True, "early_exit": True,
                "min_tokens": 2, "margin": 0.0, "large_backend": "thread",
                "large_batch": 2, "max_wait_s": 0.05},
    "correct": {"limits": {"served_gap": 0.05, "gate_miss": 0.002}},
    "reduced": [],
}
MIX = {"prompt": {"median": 40, "sigma": 0.5, "min": 16, "max": 64,
                  "grid": 16},
       "output": {"median": 6, "sigma": 0.5, "min": 3, "max": 12},
       "seed": 7, "hard_share": 0.3,
       "calibration": {"n": 8, "prompt_len": 32}}
TRAFFIC = {"mix": "tiny", "arrival": "poisson", "rate_per_s": 4.0,
           "lead_s": 0.5, "tail_s": 0.5}


def make(root: pathlib.Path, **overrides) -> pathlib.Path:
    """Write a benchmark root holding one tiny cell `tiny.x2.rate`; the
    architecture modules and the metric readers are the real ones,
    copied."""
    bench_dir = root / "bench"
    for sub in ("configs", "mixes", "traffic"):
        (bench_dir / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("archs", "metrics"):
        shutil.copytree(HERE / sub, bench_dir / sub, dirs_exist_ok=True)
    shutil.copy(HERE / "peaks.json", bench_dir / "peaks.json")
    cfg = json.loads(json.dumps(CONFIG))
    for k, v in overrides.items():
        cfg["serving"][k] = v
    (bench_dir / "configs" / "tiny.x2.json").write_text(json.dumps(cfg))
    (bench_dir / "mixes" / "tiny.json").write_text(json.dumps(MIX))
    (bench_dir / "traffic" / "tiny.rate.json").write_text(json.dumps(TRAFFIC))
    real = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    lat = [m for m in real["end_to_end"] if m["name"] in
           ("setup_s", "latency_p90_s")]
    for m in lat:
        m.pop("workloads", None)
    per = [dict(m, workloads=["tiny.x2.rate"]) for m in real["per_layer"]
           if m["moves"] == "latency_p90_s"]
    bench = {"command": ["python3", "bench/run.py"], "paths": ["bench"],
             "run_seconds": 2,
             "configs": [{"name": "tiny.x2", "source": CONFIG["source"],
                          "file": "bench/configs/tiny.x2.json",
                          "reduced": [], "why": "CPU test"}],
             "workloads": [{"name": "tiny.x2.rate", "config": "tiny.x2",
                            "traffic": "tiny.rate", "chips": 1,
                            "why": "CPU test"}],
             "end_to_end": lat, "per_layer": per}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
