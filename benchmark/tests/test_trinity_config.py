"""``trinity-mini``: its catalog keys kept but for the cut, its parameter
count and model FLOPs pinned to the arithmetic of its layers, and the
configuration at a tiny size through the ``bucketing_fit`` driver here on
the CPU (control flow and counts only: nothing timed here is a device
number)."""

import pytest

from benchmark.lib import harness as hx
from benchmark.tests.test_drivers import canned_trace  # noqa: F401
from benchmark.tests.util import run_driver

CELL = "trinity-mini-train-1c"
SLIDING, FULL = "sliding_attention", "full_attention"
# the catalog's `config` of Trinity-Mini, as published
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
CUT = ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size",
       "layer_types"]
WIDTHS = {"head_dim", "hidden_size", "intermediate_size",
          "moe_intermediate_size", "num_experts_per_tok", "sliding_window",
          "num_attention_heads", "num_key_value_heads", "num_shared_experts"}


def published():
    return hx.load_json(hx.HERE, "configs", "trinity-mini.json")


def tiny():
    cfg = published()
    cfg.update(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
               head_dim=8, sliding_window=6, intermediate_size=48,
               moe_intermediate_size=16, num_experts_published=16,
               num_experts=4, expert_offset=4, num_experts_per_tok=4,
               vocab_size=64, buckets=[16], compute_dtype="float32")
    _, cell, _, _, traffic = hx.find_cell(CELL)
    traffic.update(length_mean=16, length_std=1, batches_per_cycle=4,
                   min_slices=3, trace_steps=4,
                   reference_check={"batch": 1, "seq_len": 16})
    return cfg, traffic


def test_only_depth_experts_held_and_vocabulary_are_cut():
    cfg = published()
    assert cfg["reduced"] == CUT
    assert {k for k, v in PUBLISHED.items() if cfg[k] != v} == set(CUT)
    assert not [k for k in CUT
                if k in WIDTHS or k.endswith(("_dim", "_rank"))]
    # one leading dense layer, then one whole 3:1 period: published layers
    # 0 and 2-5
    kept = [PUBLISHED["layer_types"][i] for i in (0, 2, 3, 4, 5)]
    assert cfg["layer_types"] == kept == [SLIDING, SLIDING, FULL, SLIDING,
                                          SLIDING]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (5, 1)
    # the floors: 8 routed experts a layer, an eighth of the vocabulary
    assert cfg["num_experts"] == 8 and cfg["num_experts_published"] == 128
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["optimizer"]["learning_rate"] == 1e-6
    assert "16 chips share each layer" in cfg["deployment"]


def test_parameters_and_model_flops_are_the_arithmetic_of_the_layers():
    cfg = published()
    builder = hx.config_module("configs", "trinity-mini")
    h, t = 2048, 4096
    attention = 3 * h * 32 * 128 + 2 * h * 4 * 128    # q, gate, o; k, v
    assert attention == 27262976
    dense, expert = 3 * h * 6144, 3 * h * 1024
    router = 128 * h
    norms = 4 * h + 2 * 128
    params = (5 * (attention + norms) + dense
              + 4 * (expert + router + 128 + 8 * expert)
              + 2 * 25024 * h + h)
    assert cfg["parameters"] == params == 504147712   # 8.07 GB at 16 B
    band = 2048 * 2049 // 2 + (t - 2048) * 2048       # 6.3 M pairs a head
    assert builder.band_pairs(t, 2048) == band == 6292480
    assert builder.band_pairs(8192, 2048) == 14681088  # 33.6 M the triangle
    scores = 2 * 32 * 128 * (4 * band / t + t / 2)
    macs = (5 * attention + scores + dense
            + 4 * (expert + router + 0.5 * expert) + h * 25024)
    assert builder.forward_macs_per_token(cfg) == macs
    assert builder.train_flops_per_unit(cfg) == 3 * 2 * macs  # 1.99 GFLOP
    shares = [round(100 * x / macs) for x in (scores, h * 25024)]
    assert shares == [20, 15]


def test_the_cell_asks_for_the_traffic_the_issue_gives():
    bench, cell, entry, _, traffic = hx.find_cell(CELL)
    assert cell["chips"] == 1 and traffic["driver"] == "bucketing_fit"
    assert entry["reduced"] == CUT
    assert {k: traffic[k] for k in (
        "batch_size", "length_mean", "length_std", "zipf_a",
        "batches_per_cycle", "cycles_per_slice", "min_slices", "trace_steps",
        "kvstore", "reference_check")} == {
        "batch_size": 1, "length_mean": 4096, "length_std": 1, "zipf_a": 0.0,
        "batches_per_cycle": 8, "cycles_per_slice": 1, "min_slices": 10,
        "trace_steps": 8, "kvstore": "device",
        "reference_check": {"batch": 1, "seq_len": 4096}}
    # the issue's pre-declared fallback from rows of 8192, and the
    # framework's own recomputation switch: both forced by memory
    assert cell["traffic"] == "packed-4k-uniform-b1"
    assert traffic["env"] == {"MXNET_BACKWARD_DO_MIRROR": "1"}
    assert len(bench["workloads"]) == 5
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        "resnet50-train-4c"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bucketing_driver_runs_the_configuration(canned_trace, dtype):  # noqa: F811
    cfg, traffic = tiny()
    cfg["compute_dtype"] = dtype
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    run = run_driver(cfg, traffic, builder_of="trinity-mini", seconds=0.3,
                     trace=1, bench=bench)
    tokens_a_step = run["obs"]["units"] / run["obs"]["steps"]
    assert 13 <= tokens_a_step <= 16           # a row of 13-16 real tokens
    assert run["obs"]["program_syncs"] == 0
    # and the reference agrees; a bfloat16 trunk at 32 features is off by
    # more than TOLERANCES, which are set at published widths on the chip
    assert run["correct"] or dtype == "bfloat16"
    assert run["failed"] == 0
    assert set(run["end_to_end"]) == {"train_tokens_per_s", "setup_s"}
    run["cell"] = dict(run["cell"], name=CELL)  # setup.* read their cell
    readers = hx.layer_readers()
    got = {n: readers[n].read(run)
           for n in hx.metrics_of(bench, CELL, "per_layer")}
    assert got["moe.layers_per_step.seq"] == 4.0
    assert got["moe.local_experts_per_step.seq"] == 4 * 4
    assert got["moe.assignments_per_step.seq"] == 4 * 16 * 4
    assert got["moe.kernel_matmuls_per_step.seq"] == 0.0   # the CPU
    assert got["attention.layers_per_step.seq"] == 5.0
    assert got["attention.window_layers_per_step.seq"] == 4.0
    # T 16 is one block of queries: each head scores the whole square
    assert got["attention.scored_pairs_per_step.seq"] == 5 * 4 * 16 * 16
    assert got["dispatch.bucket_switches_per_step.seq"] == 0.0
    missing = [n for n, v in got.items() if v is None]
    assert not missing, missing
    # a program with no such counter: 0, and no error
    run["obs"]["tm0"] = run["obs"]["tm1"] = {}
    assert readers["moe.local_experts_per_step.seq"].read(run) == 0
