"""``zaya1-8b``: its catalog keys kept but for the cut, its parameter count,
model FLOPs and the operators' roofline arithmetic pinned to the arithmetic
of its layers, and the configuration at a tiny size through the
``bucketing_fit`` driver here on the CPU (control flow and counts only:
nothing timed here is a device number)."""

import pytest

from benchmark.lib import harness as hx
from benchmark.tests.test_drivers import canned_trace  # noqa: F401
from benchmark.tests.util import run_driver

NAME, CELL = "zaya1-8b", "zaya1-8b-train-1c"
# the catalog's `config` of ZAYA1-8B, as published
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048,
    "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
    "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}
CUT = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW = ["moe.graph_routed_layers_per_step.seq",
       "conv.grouped_layers_per_step.seq", "causal_conv_roofline.seq"]
T = 8192


def published():
    return hx.load_json(hx.HERE, "configs", NAME + ".json")


def tiny():
    cfg = published()
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, moe_intermediate_size=16, router_hidden_size=16,
               num_experts_published=8, num_experts=4, expert_offset=4,
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
    assert set(PUBLISHED) <= set(cfg)
    assert not [k for k in CUT if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    # four of the forty layers, each a mixer and a mixture (the period is 1)
    assert cfg["num_hidden_layers"] == 4
    assert cfg["num_hidden_layers_published"] == 40
    assert set(cfg["layer_types"]) == {"hybrid"}
    # the floors: 8 routed experts a layer, an eighth of the vocabulary
    assert cfg["num_experts"] == 8 and cfg["num_experts_published"] == 16
    assert cfg["expert_offset"] == 0 and cfg["num_experts_per_tok"] == 1
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"] == 262272
    assert cfg["tie_word_embeddings"] is True
    assert cfg["optimizer"]["learning_rate"] == 1e-6
    assert "group of 8 chips" in cfg["deployment"]
    assert "shared by a pair" in cfg["deployment"]
    for key in ("residual_scaling", "mixer_projections", "conv_qk", "qk_mean",
                "value_shift", "qk_norm_temperature", "positions",
                "softmax_scale", "router", "gelu", "selection_bias",
                "tied_head", "described_as_mod"):
        assert key in cfg["assumed"], key


def test_parameters_and_model_flops_are_the_arithmetic_of_the_layers():
    cfg = published()
    builder = hx.config_module("configs", NAME)
    h, d = 2048, 128
    q, k, v, o = h * 8 * d, h * 2 * d, 2 * h * d, 8 * d * h
    conv0, conv1 = 1280 * 2 + 1280, 10 * d * d * 2 + 1280
    mixer = q + k + v + o + conv0 + conv1 + 2
    assert (q, k, v, o) == (2097152, 524288, 524288, 2097152)
    assert mixer == 5575682
    router = h * 256 + 256 + 256 + 2 * 256 * 256 + 256 * 16
    assert router == 659968
    experts = 8 * 3 * h * 2048
    assert experts == 100663296
    scales = 2 * h + 8 * h
    first = mixer + router + experts + scales
    later = first + 256                        # the carry's gamma
    assert (first, later) == (106919426, 106919682)
    params = first + 3 * later + 32784 * h + h     # the tied table once
    assert cfg["parameters"] == params == 494822152    # 7.92 GB at 16 B
    projections = q + k + v + o
    assert builder.mixer_macs_per_token(cfg) == projections
    convs = 1280 * 2 + 10 * d * d * 2
    assert builder.conv_macs_per_token(cfg) == convs == 330240
    scores = 8 * (T // 2) * 2 * d
    assert builder.score_macs_per_token(cfg) == scores == 8388608
    routed = h * 256 + 2 * 256 * 256 + 256 * 16
    assert builder.router_macs_per_token(cfg) == routed == 659456
    macs = 4 * (projections + convs + scores + routed
                + 0.5 * 3 * h * 2048) + h * 32784
    assert builder.forward_macs_per_token(cfg) == macs == 150792192
    assert builder.train_flops_per_unit(cfg) == 3 * 2 * macs  # 0.90 GFLOP
    shares = [round(100 * x / macs) for x in (
        4 * scores, 4 * (projections + convs), 4 * 0.5 * 3 * h * 2048,
        4 * routed, h * 32784)]
    assert shares == [22, 15, 17, 2, 45]
    # a step of 8192 tokens: 7.4 TFLOP
    assert round(builder.train_flops_per_unit(cfg) * T / 1e12, 1) == 7.4


def test_operator_work_is_a_hand_count():
    """A step's least work: ``RingAttention`` the causal triangle exactly, 8
    query heads over 2 key/value heads of 128; ``MoE`` the expected half of
    the top-1 assignments through the three products and no router product
    (the router is the graph's); ``CausalConv1D`` both convolutions, three
    rows of 1280 channels across HBM once each way."""
    cfg = published()
    builder = hx.config_module("configs", NAME)
    traffic = hx.load_json(hx.HERE, "traffic", "packed-8k-uniform-b1.json")
    work = builder.operator_work(cfg, traffic)
    assert set(work) == {"RingAttention", "MoE", "CausalConv1D"}
    pairs = T * (T + 1) // 2
    assert work["RingAttention"] == {
        "flops": 4 * 8 * pairs * 3 * 2 * (128 + 128),
        "bytes": 4 * 2 * 2 * T * (8 + 2) * (128 + 128)}
    assignments = T * 1 * 8 / 16
    assert work["MoE"]["flops"] == 4 * 3 * 2 * assignments * 3 * 2048 * 2048
    assert work["MoE"]["bytes"] == 4 * (
        2 * 4 * 8 * 3 * 2048 * 2048        # the held experts and gradients
        + 2 * 2 * 2 * T * 2048             # rows in and out, and gradients
        + 2 * 4 * T * 16)                  # float32 logits and gradient
    assert work["CausalConv1D"]["flops"] == 4 * 3 * 2 * T * (
        1280 * 2 + 10 * 128 * 128 * 2)
    assert work["CausalConv1D"]["bytes"] == 4 * (
        2 * 2 * T * 3 * 1280
        + 2 * 4 * (1280 * 2 + 10 * 128 * 128 * 2 + 2 * 1280))
    # two taps: bound by bytes on a v5e (197 TFLOP/s over 819 GB/s = 240)
    assert work["CausalConv1D"]["flops"] / work["CausalConv1D"]["bytes"] < 240


def test_the_cell_asks_for_the_traffic_the_issue_gives():
    bench, cell, entry, cfg, traffic = hx.find_cell(CELL)
    assert cell["chips"] == 1 and traffic["driver"] == "bucketing_fit"
    assert cell["config"] == NAME and entry["reduced"] == CUT
    assert entry["source"] == cfg["source"]
    assert entry["file"] == "benchmark/configs/zaya1-8b.json"
    assert cell["traffic"] == traffic["name"] == "packed-8k-uniform-b1"
    assert traffic["length_mean"] == T and cfg["buckets"] == [T]
    assert traffic["reference_check"] == {"batch": 1, "seq_len": T}
    assert traffic["env"] == {"MXNET_BACKWARD_DO_MIRROR": "1"}
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        "resnet50-train-4c"]
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    reported = hx.metrics_of(bench, CELL, "per_layer")
    for name in NEW + ["attention.kernel_layers_per_step.seq",
                       "attention.scored_pairs_per_step.seq",
                       "moe.local_experts_per_step.seq",
                       "moe.kernel_matmuls_per_step.seq",
                       "step.kept_residual_nodes_per_step.seq",
                       "kernels.mfu_pct.seq", "moe_roofline.seq",
                       "attention_roofline.seq"]:
        assert name in reported, name
    for name in ("attention.window_layers_per_step.seq",
                 "attention.latent_layers_per_step.seq",
                 "attention.lanes_per_pair.seq",
                 "linear_attention.layers_per_step.seq",
                 "linear_attention_roofline.seq",
                 "step.stacked_wgrad_per_step.seq"):
        assert name not in reported, name
    assert hx.metrics_of(bench, CELL, "end_to_end") == [
        "train_tokens_per_s", "setup_s"]
    # the three metrics this configuration brought are its cell's alone
    assert [m["name"] for m in bench["per_layer"]][-3:] == NEW
    for m in bench["per_layer"][-3:]:
        assert m["workloads"] == [CELL]
    readers = hx.layer_readers()
    for name, layer, source in zip(NEW, ["fused step", "fused step",
                                         "kernels"],
                                   ["program_counter", "program_counter",
                                    "device_trace"]):
        assert (readers[name].NAME, readers[name].LAYER,
                readers[name].SOURCE) == (name, layer, source)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bucketing_driver_runs_the_configuration(canned_trace, dtype):  # noqa: F811
    cfg, traffic = tiny()
    cfg["compute_dtype"] = dtype
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    run = run_driver(cfg, traffic, builder_of=NAME, seconds=0.3, trace=1,
                     bench=bench)
    tokens_a_step = run["obs"]["units"] / run["obs"]["steps"]
    assert 13 <= tokens_a_step <= 16           # a row of 13-16 real tokens
    assert run["obs"]["program_syncs"] == 0
    # and the reference agrees; a bfloat16 trunk at 64 features is off by
    # more than TOLERANCES, which are set at published widths on the chip
    assert run["correct"] or dtype == "bfloat16"
    assert run["failed"] == 0
    assert set(run["end_to_end"]) == {"train_tokens_per_s", "setup_s"}
    run["cell"] = dict(run["cell"], name=CELL)  # setup.* read their cell
    readers = hx.layer_readers()
    got = {n: readers[n].read(run)
           for n in hx.metrics_of(bench, CELL, "per_layer")}
    assert got["moe.graph_routed_layers_per_step.seq"] == 4.0
    assert got["conv.grouped_layers_per_step.seq"] == 4.0
    assert got["attention.layers_per_step.seq"] == 4.0
    assert got["attention.kernel_layers_per_step.seq"] == 0.0   # the CPU
    # T 16 is one block of queries: each head scores the whole square
    assert got["attention.scored_pairs_per_step.seq"] == 4 * 4 * 16 * 16
    assert got["moe.layers_per_step.seq"] == 4.0
    assert got["moe.local_experts_per_step.seq"] == 4 * 4
    assert got["moe.assignments_per_step.seq"] == 4 * 16 * 1
    assert got["moe.kernel_matmuls_per_step.seq"] == 0.0   # the CPU
    # four attention nodes and four expert layers keep their residuals
    assert got["step.kept_residual_nodes_per_step.seq"] == 8.0
    assert got["dispatch.bucket_switches_per_step.seq"] == 0.0
    # the canned trace has no row named CausalConv1D, MoE or RingAttention
    rooflines = {n for n in got if n.endswith("_roofline.seq")}
    assert rooflines == {"moe_roofline.seq", "attention_roofline.seq",
                         "causal_conv_roofline.seq"}
    missing = [n for n, v in got.items() if v is None and n not in rooflines]
    assert not missing, missing
    # a program with neither counter (the parent): 0, and no error
    run["obs"]["tm0"] = run["obs"]["tm1"] = {}
    assert readers["moe.graph_routed_layers_per_step.seq"].read(run) == 0
    assert readers["conv.grouped_layers_per_step.seq"].read(run) == 0


def test_the_roofline_reads_the_rows_named_causal_conv():
    """``causal_conv_roofline.seq`` over a table by operator: all the
    device time under ``CausalConv1D`` (forward, backward, recompute)
    against the builder's count; None where the table has no such row or
    the builder no such term."""
    cfg = published()
    builder = hx.config_module("configs", NAME)
    traffic = hx.load_json(hx.HERE, "traffic", "packed-8k-uniform-b1.json")
    readers = hx.layer_readers()
    rows = [{"operator": "CausalConv1D", "pass": p, "ms": ms}
            for p, ms in (("forward", 8.0), ("backward", 16.0),
                          ("recompute", 8.0))]
    rows.append({"operator": "MoE", "pass": "forward", "ms": 100.0})
    run = {"builder": builder, "config": cfg, "traffic": traffic,
           "obs": {"trace": {"table": {"unscoped_share": 0.0,
                                       "by_operator": rows}},
                   "trace_slice": [8], "peak_flops": 197e12,
                   "peak_bytes_per_s": 819e9}}
    work = builder.operator_work(cfg, traffic)["CausalConv1D"]
    want = 100.0 * (work["bytes"] / 819e9) / (32e-3 / 8)
    assert readers["causal_conv_roofline.seq"].read(run) == pytest.approx(want)
    assert 10 < want < 20                       # 0.63 ms of a 4 ms step
    run["obs"]["trace"]["table"]["by_operator"] = rows[-1:]
    assert readers["causal_conv_roofline.seq"].read(run) is None
    other = hx.config_module("configs", "qwen3-next-80b-a3b")
    run["obs"]["trace"]["table"]["by_operator"] = rows
    assert readers["causal_conv_roofline.seq"].read(
        dict(run, builder=other, config=hx.load_json(
            hx.HERE, "configs", "qwen3-next-80b-a3b.json"))) is None
