"""The Mellum architecture module (`archs/mellum.py`): a cell of it runs
on the CPU through the harness from added files only, its own reference
decides `correct`, and its counts and reader price what the layers need."""
import json
import types

import pytest

import model
import run
import tinybench

TIER = {"arch": "mellum", "name": "tiny-mellum", "hidden_size": 64,
        "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16,
        "moe_intermediate_size": 32, "vocab_size": 512,
        "rms_norm_eps": 1e-6, "num_experts": 4, "num_experts_published": 8,
        "first_expert": 0, "expert_parallel": 2, "num_experts_per_tok": 2,
        "norm_topk_prob": True, "sliding_window": 16,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "rope_parameters": {
            "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                               "factor": 16,
                               "original_max_position_embeddings": 8192,
                               "beta_fast": 32, "beta_slow": 1,
                               "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}}}


def _mellum_cell(root, fault=None):
    """A tiny cell whose tier 0 is the Mellum module (with `fault` planted
    in its reference), under the tiny dense top tier."""
    bench_dir = root / "bench"
    if fault is not None:
        path = bench_dir / "archs" / "mellum.py"
        path.write_text(path.read_text().replace(f"{fault} = True",
                                                 f"{fault} = False"))
    cfg = json.loads((bench_dir / "configs" / "tiny.x2.json").read_text())
    cfg["name"] = "tinymel.x2"
    cfg["tiers"][0] = dict(TIER)
    cfg["tiers"][1]["vocab_size"] = TIER["vocab_size"]
    (bench_dir / "configs" / "tinymel.x2.json").write_text(json.dumps(cfg))
    bench = run.load_bench(root)
    bench["configs"].append(dict(bench["configs"][0], name="tinymel.x2",
                                 file="bench/configs/tinymel.x2.json"))
    bench["workloads"].append({"name": "tinymel.x2.rate",
                               "config": "tinymel.x2", "traffic": "tiny.rate",
                               "chips": 1, "why": "CPU test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return "tinymel.x2.rate"


@pytest.mark.parametrize("fault", [None, "USE_WINDOW", "USE_YARN"],
                         ids=["sound", "reference_ignores_window",
                              "reference_drops_yarn"])
def test_mellum_from_added_files_only(tmp_path, fault):
    """Sound, the run is correct; a reference that attends past the window
    on the sliding layers, or uses plain rope on the full layers, reads a
    served_gap over the limit."""
    root = tinybench.make(tmp_path)
    cell = _mellum_cell(root, fault)
    res = run.run_cell(root, cell, 2**31 + 77, 2.0, trace=False,
                       log=lambda *_: None)
    gap = res["checks"]["served_gap"]
    assert res["attempted"] > 0 and res["failed"] == 0
    if fault is None:
        assert res["correct"], res["checks"]
    else:
        assert not res["correct"] and gap["value"] > gap["limit"], \
            res["checks"]


@pytest.fixture(scope="module")
def mel():
    mod = model.load_arch(tinybench.HERE, "mellum")
    return mod, mod.tier(TIER)


def test_counts_clip_the_window_and_price_hit_experts(mel):
    mod, t = mel
    d, f, H, KV, hd = 64, 32, 4, 2, 16
    pos = [5, 40]
    calls = mod.paged_attn_calls(t, pos, 2)
    assert len(calls) == 4
    qo = 2 * 2 * H * hd * 2
    win = (6 + 16)                        # the sliding layers' window
    full = (6 + 41)
    for i in range(3):
        assert calls[i] == (2 * win * KV * hd * 2 + qo, 4 * H * hd * win)
    assert calls[3] == (2 * full * KV * hd * 2 + qo, 4 * H * hd * full)
    got = mod.moe_calls(t, [0, 1, 3], [0, 2, 5])
    assert got == [(0, 0), (3 * d * f * 2 + 2 * 2 * d * 2, 2 * 6 * d * f),
                   (3 * 3 * d * f * 2 + 5 * 2 * d * 2, 5 * 6 * d * f)]
    # expected held assignments: top_k * n_held / n_experts = 1 a layer
    per_row = 2 * (4 * (d * H * hd + 2 * d * KV * hd + H * hd * d
                        + d * 8 + 1 * 3 * d * f) + d * 512)
    att = 3 * 4 * H * hd * win + 4 * H * hd * full
    assert mod.decode_step_flops(t, pos) == per_row * 2 + att
    # every expert's weights a share of the layer's: 4 of 8 held
    assert t.n_params == 4 * (2 * d + 2 * d * H * hd + 2 * d * KV * hd
                              + d * 8 + 4 * 3 * d * f) + 2 * 512 * d + d


def test_moe_roofline_reads_the_audit_counters(mel):
    mod, t = mel
    reader = run.load_reader(tinybench.HERE, "moe_roofline.lat")
    peak = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    audit = [{"event": "moe", "t": 1.0, "step": 0, "hit": [1, 0, 2, 1],
              "assign": [1, 0, 2, 1]},
             {"event": "moe", "t": 1.5, "step": 1, "hit": [2, 1, 1, 0],
              "assign": [3, 1, 1, 0]},
             {"event": "moe", "t": 9.0, "step": 2, "hit": [4, 4, 4, 4],
              "assign": [9, 9, 9, 9]}]
    plane = {"ops": {"moe_gmm_decode.3": (6e-3, 8), "fusion.1": (1.0, 9)},
             "modules": {}}
    fake = types.SimpleNamespace(
        archs=[mod], tiers=[t], peak=peak, audit=audit,
        trace_window=(0.5, 2.0),
        trace={"planes": {"/device:TPU:0": plane}, "busy_s": 1.0})
    # every layer's call counts, those of layers with no hit (needing
    # nothing: no expert read, no activation moved) included
    need = [max(b / 1e9, f / 1e12) for e in audit[:2]
            for b, f in mod.moe_calls(t, e["hit"], e["assign"])]
    assert len(need) == 8 and need.count(0.0) == 2
    want = 100.0 * sum(need) / len(need) * 8 / 6e-3
    assert reader(fake) == pytest.approx(want)
    # a program without expert counters leaves the metric out
    assert reader(types.SimpleNamespace(**dict(vars(fake), audit=[]))) is None
