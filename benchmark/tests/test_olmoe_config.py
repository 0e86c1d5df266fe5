"""``olmoe-1b-7b``: its model FLOPs pinned to the arithmetic of its layer,
its catalog keys kept, and the configuration at a tiny size through the
``bucketing_fit`` driver here on the CPU (control flow and counts only:
nothing timed here is a device number)."""

import pytest

from benchmark.lib import harness as hx
from benchmark.tests.test_drivers import canned_trace, check_window  # noqa: F401
from benchmark.tests.util import run_driver

CELL = "olmoe-1b7b-train-1c"
# the catalog's `config` of OLMoE-1B-7B-0125-Instruct, as published
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


def published():
    return hx.load_json(hx.HERE, "configs", "olmoe-1b-7b.json")


def tiny():
    cfg = published()
    cfg.update(hidden_size=32, num_attention_heads=2, num_key_value_heads=2,
               num_experts=8, intermediate_size=16, num_experts_per_tok=2,
               vocab_size=64, num_hidden_layers=2, buckets=[16],
               compute_dtype="float32")
    _, cell, _, _, traffic = hx.find_cell(CELL)
    traffic.update(length_mean=16, length_std=1, batches_per_cycle=4,
                   min_slices=3, trace_steps=4,
                   reference_check={"batch": 1, "seq_len": 16})
    return cfg, traffic


def test_only_the_depth_is_cut():
    cfg = published()
    assert cfg["reduced"] == ["num_hidden_layers"]
    changed = {k for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == {"num_hidden_layers"} and cfg["num_hidden_layers"] == 1


def test_model_flops_are_the_arithmetic_of_the_layer_as_run():
    cfg = published()
    builder = hx.config_module("configs", "olmoe-1b-7b")
    head = 2048 * 50304                 # 103.0 M multiply-adds a token
    experts = 8 * 3 * 2048 * 1024       # 50.3 M: eight of 64, gate up down
    projections = 4 * 2048 * 2048       # 16.8 M: q, k, v, o
    scores = 2 * (4096 // 2) * 2048     # 8.4 M: causal, q.k and p.v
    router = 64 * 2048                  # 0.13 M
    macs = head + experts + projections + scores + router
    assert builder.forward_macs_per_token(cfg) == macs == 178651136
    assert builder.train_flops_per_unit(cfg) == 3 * 2 * macs  # 1.07 GFLOP
    shares = [round(100 * x / macs) for x in (head, experts,
                                              projections + scores)]
    assert shares == [58, 28, 14]
    # the parameters the file states: 16 B each fill 10.0 GB of the chip
    params = (2 * 2048 * 50304 + 4 * 2048 * 2048 + 64 * 2048
              + 3 * 64 * 2048 * 1024 + 5 * 2048)
    assert cfg["parameters"] == params == 625616896


def test_the_cell_asks_for_the_traffic_the_issue_gives():
    _, cell, _, _, traffic = hx.find_cell(CELL)
    assert cell["chips"] == 1 and traffic["driver"] == "bucketing_fit"
    tokens = traffic["batch_size"] * 4096
    assert tokens * traffic["batches_per_cycle"] == 131072  # a pass
    assert {k: traffic[k] for k in (
        "length_mean", "length_std", "zipf_a", "cycles_per_slice",
        "min_slices", "trace_steps", "kvstore", "reference_check")} == {
        "length_mean": 4096, "length_std": 1, "zipf_a": 1.0,
        "cycles_per_slice": 1, "min_slices": 10,
        "trace_steps": 16, "kvstore": "device",
        "reference_check": {"batch": 1, "seq_len": 4096}}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bucketing_driver_runs_the_configuration(canned_trace, dtype):  # noqa: F811
    cfg, traffic = tiny()
    cfg["compute_dtype"] = dtype
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    run = run_driver(cfg, traffic, builder_of="olmoe-1b-7b", seconds=0.3,
                     trace=1, bench=bench)
    tokens_a_step = run["obs"]["units"] / run["obs"]["steps"]
    assert 13 <= tokens_a_step <= 16           # a row of 13-16 real tokens
    assert run["obs"]["program_syncs"] == 0
    assert run["correct"]                       # and the reference agrees
    assert set(run["end_to_end"]) == {"train_tokens_per_s", "setup_s"}
    run["cell"] = dict(run["cell"], name=CELL)  # setup.* read their cell
    readers = hx.layer_readers()
    got = {n: readers[n].read(run)
           for n in hx.metrics_of(bench, CELL, "per_layer")}
    assert got["moe.layers_per_step.seq"] == 2.0
    assert got["attention.layers_per_step.seq"] == 2.0
    assert got["moe.assignments_per_step.seq"] == 2 * 16 * 2
    assert got["dispatch.bucket_switches_per_step.seq"] == 0.0
    missing = [n for n, v in got.items() if v is None]
    assert not missing, missing
    # and the other cells do not list them
    for other in ("resnet50-train-1c", "lstm-ptb-train-1c"):
        assert not [m for m in hx.metrics_of(bench, other, "per_layer")
                    if m.startswith(("moe.", "attention."))]
