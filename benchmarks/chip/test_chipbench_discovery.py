"""Cells and architectures are found by name, and the command refuses to
run off the chip."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

import run
import tinybench
import traffic

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def test_new_cell_from_added_files_only(tmp_path):
    """A configuration, a mix, a traffic file and a metric reader added as
    files, with BENCHMARK.json entries, make a new cell loadable: nothing
    else is edited."""
    root = tinybench.make(tmp_path)
    bench_dir = root / "bench"
    (bench_dir / "metrics" / "prompt_tokens_mean.lat.py").write_text(
        "def read(run):\n"
        "    return sum(r.prompt_len for r in run.reqs) / len(run.reqs)\n")
    mix = json.loads((bench_dir / "mixes" / "tiny.json").read_text())
    mix["prompt"]["median"] = 24
    (bench_dir / "mixes" / "tiny2.json").write_text(json.dumps(mix))
    (bench_dir / "traffic" / "tiny2.rate.json").write_text(json.dumps(
        {"mix": "tiny2", "rate_per_s": 2.0, "lead_s": 0.0, "tail_s": 0.0}))
    bench = run.load_bench(root)
    bench["workloads"].append({"name": "tiny.x2.short", "config": "tiny.x2",
                               "traffic": "tiny2.rate", "chips": 1,
                               "why": "shorter prompts"})
    bench["per_layer"].append({"name": "prompt_tokens_mean.lat",
                               "unit": "tokens", "better": "lower",
                               "source": "program_counter",
                               "layer": "scheduler",
                               "moves": "latency_p90_s",
                               "workloads": ["tiny.x2.short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    bench = run.load_bench(root)
    cell = run.cell_of(bench, "tiny.x2.short")
    assert "prompt_tokens_mean.lat" in run.metric_names(bench, cell,
                                                        "per_layer")
    assert "prompt_tokens_mean.lat" not in run.metric_names(
        bench, run.cell_of(bench, "tiny.x2.rate"), "per_layer")
    assert run.metric_names(bench, cell, "end_to_end") == [
        "setup_s", "latency_p90_s"]
    s = traffic.build_schedule(bench_dir, cell["traffic"], 9, 4.0, 512)
    assert len(s.specs) == 8 and all(x.measured for x in s.specs)
    reader = run.load_reader(bench_dir, "prompt_tokens_mean.lat")
    fake = type("Run", (), {"reqs": [type("R", (), {"prompt_len": 4})()]})
    assert reader(fake) == 4


def _add_config(root, name, edit):
    """A copy of the tiny configuration under `name`, changed by `edit`,
    and a cell `<name>.rate` that runs it on the tiny traffic."""
    bench_dir = root / "bench"
    cfg = json.loads((bench_dir / "configs" / "tiny.x2.json").read_text())
    cfg["name"] = name
    edit(cfg)
    (bench_dir / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    bench = run.load_bench(root)
    bench["configs"].append(dict(bench["configs"][0], name=name,
                                 file=f"bench/configs/{name}.json"))
    bench["workloads"].append({"name": f"{name}.rate", "config": name,
                               "traffic": "tiny.rate", "chips": 1,
                               "why": "CPU test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return f"{name}.rate"


@pytest.mark.parametrize("reference_biases", [True, False],
                         ids=["sound", "reference_drops_biases"])
def test_new_architecture_from_added_files_only(tmp_path, reference_biases):
    """An architecture module (the dense block with learned Q/K/V biases)
    and a configuration whose tiers name it, added as files with their
    BENCHMARK.json entries, run as a cell: nothing else is edited. The
    module's own reference decides `correct`: with the biases the run is
    correct, and a reference that drops them fails served_gap."""
    root = tinybench.make(tmp_path)
    src = (HERE / "testdata" / "dense_qkv_bias.py").read_text()
    if not reference_biases:
        src = src.replace("REFERENCE_BIASES = True",
                          "REFERENCE_BIASES = False")
    (root / "bench" / "archs" / "dense_qkv_bias.py").write_text(src)

    def biased(cfg):
        for t in cfg["tiers"]:
            t["arch"] = "dense_qkv_bias"

    cell = _add_config(root, "tinyqkvb.x2", biased)
    res = run.run_cell(root, cell, 2**31 + 21, 2.0, trace=False,
                       log=lambda *_: None)
    gap = res["checks"]["served_gap"]
    assert res["attempted"] > 0 and res["failed"] == 0
    if reference_biases:
        assert res["correct"], res["checks"]
    else:
        assert not res["correct"] and gap["value"] > gap["limit"], \
            res["checks"]


@pytest.mark.parametrize("arch", [None, "no_such_arch"],
                         ids=["no_arch", "missing_module"])
def test_tier_without_an_architecture_stops_the_run(tmp_path, arch):
    root = tinybench.make(tmp_path)

    def edit(cfg):
        cfg["tiers"][1].pop("arch")
        if arch is not None:
            cfg["tiers"][1]["arch"] = arch

    cell = _add_config(root, "tinybad.x2", edit)
    with pytest.raises(SystemExit) as e:
        run.run_cell(root, cell, 3, 2.0, trace=False, log=lambda *_: None)
    msg = str(e.value)
    assert "bench/configs/tinybad.x2.json" in msg, msg
    assert "tier 1 (tiny-large)" in msg, msg
    assert (arch or "names no architecture") in msg, msg


def test_every_benchmark_metric_has_a_reader():
    bench = run.load_bench(ROOT)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        for t in cfg["tiers"]:
            assert (HERE / "archs" / f"{t['arch']}.py").is_file(), t
    for w in bench["workloads"]:
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
        for kind in ("end_to_end", "per_layer"):
            assert run.metric_names(bench, w, kind), (w["name"], kind)


def test_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "ilm2x2.chat.rate", "--seed", "0", "--seconds", "10", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
