"""``kimi-linear-48b-a3b``: its catalog keys kept but for the cut, its
parameter count, model FLOPs and operators' least work pinned to the
arithmetic of its layers, and the configuration at a tiny size through the
``bucketing_fit`` driver here on the CPU (control flow and counts only:
nothing timed here is a device number). Every check of ``BENCHMARK.json``
asserts membership, never a length or a position of a list."""

import pytest

from benchmark.lib import harness as hx
from benchmark.tests.test_drivers import canned_trace  # noqa: F401
from benchmark.tests.util import run_driver

NAME, CELL = "kimi-linear-48b-a3b", "kimi-linear-train-1c"
# the catalog's `config` of Kimi-Linear-48B-A3B-Instruct, as published
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
CUT = ["num_hidden_layers", "num_experts", "vocab_size", "linear_attn_config"]
T = 4096
H = 2304


def published():
    return hx.load_json(hx.HERE, "configs", NAME + ".json")


def tiny():
    cfg = published()
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
               kv_lora_rank=32, intermediate_size=96,
               moe_intermediate_size=16, num_experts_published=16,
               num_experts=4, expert_offset=4, num_experts_per_token=3,
               vocab_size=64, buckets=[16], compute_dtype="float32",
               linear_attn_config=dict(cfg["linear_attn_config"],
                                       num_heads=4, head_dim=16))
    _, cell, _, _, traffic = hx.find_cell(CELL)
    traffic.update(length_mean=16, length_std=1, batches_per_cycle=4,
                   min_slices=3, trace_steps=4,
                   reference_check={"batch": 1, "seq_len": 16})
    return cfg, traffic


def test_only_depth_experts_held_vocabulary_and_the_layer_lists_are_cut():
    cfg = published()
    assert cfg["reduced"] == CUT
    assert {k for k, v in PUBLISHED.items() if cfg[k] != v} == set(CUT)
    assert set(PUBLISHED) <= set(cfg)
    # no width among them, at the top level or inside the nested group
    assert not [k for k in CUT if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    lin, was = cfg["linear_attn_config"], PUBLISHED["linear_attn_config"]
    assert {k for k in was if lin[k] != was[k]} == {"kda_layers",
                                                    "full_attn_layers"}
    assert cfg["linear_attn_config_published"] == was
    # published layers 1-5: a whole KDA, KDA, KDA, latent period and one more
    assert lin["kda_layers"] == [1, 2, 3, 5] and lin["full_attn_layers"] == [4]
    assert [i for i in was["kda_layers"] if i <= 5] == lin["kda_layers"]
    assert [i for i in was["full_attn_layers"] if i <= 5] \
        == lin["full_attn_layers"]
    assert cfg["num_hidden_layers"] == 5 and cfg["first_k_dense_replace"] == 1
    assert cfg["num_hidden_layers_published"] == 27
    # the floors: 8 routed experts a layer, an eighth of the vocabulary, the
    # leading dense layer and four that follow
    assert cfg["num_experts"] == 8 and cfg["num_experts_published"] == 256
    assert cfg["expert_offset"] == 0
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"] == 163840
    assert cfg["optimizer"]["learning_rate"] == 1e-6
    assert "32 chips share each layer" in cfg["deployment"]
    for key in ("kda_projections", "conv", "qk_l2norm", "gate", "rule",
                "gated_norm", "latent", "softmax_scale", "shared_experts",
                "router", "expert_bias", "head_dim",
                "linear_attention_init", "gate_init", "parameters"):
        assert key in cfg["assumed"], key


def test_parameters_and_model_flops_are_the_arithmetic_of_the_layers():
    cfg = published()
    builder = hx.config_module("configs", NAME)
    width = 32 * 128
    qkv, taps, o = 3 * H * width, 3 * width * 4, width * H
    low_rank = H * 128 + 128 * width
    assert (qkv, taps, low_rank, o) == (28311552, 49152, 819200, 9437184)
    kda = qkv + taps + low_rank + H * 32 + 32 + width + low_rank + 128 + o
    assert kda == 39514272
    q, kv_a = H * 32 * 192, H * (512 + 64)
    kv_b = 512 * 32 * (128 + 128)
    latent = q + kv_a + 512 + kv_b + o
    assert latent == 29114880
    expert = 3 * H * 1024
    sparse = 8 * expert + expert + 256 * H + 256
    assert sparse == 64291072
    dense = 3 * H * 9216
    assert dense == 63700992
    layers = 4 * kda + latent + dense + 4 * sparse + 5 * 2 * H
    assert layers == 508060288
    params = layers + 2 * 20480 * H + H
    assert cfg["parameters"] == params == 602434432      # 9.64 GB at 16 B
    # model FLOPs: the rule in its recurrent form, 3 x 128 x 128 a head
    kda_macs = 4 * H * width + 2 * low_rank + H * 32 + 4 * 3 * width \
        + 3 * 32 * 128 * 128
    assert builder.kda_macs_per_token(cfg) == kda_macs
    latent_macs = q + kv_a + kv_b + o + 32 * (T // 2) * (192 + 128)
    assert builder.latent_macs_per_token(cfg) == latent_macs
    macs = (4 * kda_macs + latent_macs + dense
            + 4 * (expert + 256 * H + 8 * 8 / 256 * expert) + H * 20480)
    assert builder.forward_macs_per_token(cfg) == macs
    assert builder.train_flops_per_unit(cfg) == 3 * 2 * macs
    assert round(macs / 1e6) == 363                 # ISSUE 48's count
    # the KDA layers' mixers are 45% of the FLOPs; a step of 4096: 8.9 TFLOP
    assert round(100 * 4 * kda_macs / macs) == 45
    assert round(builder.train_flops_per_unit(cfg) * T / 1e12, 1) == 8.9


def test_operator_work_counts_the_gate_a_channel_and_exact_pairs():
    """``GatedDeltaRule``: ``lib/flops.py``'s recurrent form plus the bytes
    of a gate of 128 float32 numbers a head and token, with its gradient;
    ``CausalConv1D``: four taps over 12 288 channels, the row in and out;
    ``RingAttention`` on the one latent layer at 192 / 128; ``MoE`` on the
    four expert layers."""
    from benchmark.lib import flops

    cfg = published()
    builder = hx.config_module("configs", NAME)
    traffic = hx.load_json(hx.HERE, "traffic", "packed-4k-uniform-b1.json")
    work = builder.operator_work(cfg, traffic)
    assert set(work) == {"MoE", "RingAttention", "GatedDeltaRule",
                         "CausalConv1D"}
    plain = flops.delta_rule_work(T, 32, 32, 128, 128)
    assert work["GatedDeltaRule"] == {
        "flops": 4 * plain["flops"],
        "bytes": 4 * (plain["bytes"] + 2 * 4 * T * 32 * 128)}
    assert work["GatedDeltaRule"]["flops"] == 4 * 3 * 2 * 3 * T * 32 * 128 * 128
    channels = 3 * 32 * 128
    assert work["CausalConv1D"] == {
        "flops": 4 * 3 * 2 * T * channels * 4,
        "bytes": 4 * (2 * 2 * T * 2 * channels + 2 * 4 * channels * 4)}
    pairs = T * (T + 1) // 2
    assert work["RingAttention"] == {
        "flops": 32 * pairs * 3 * 2 * (192 + 128),
        "bytes": 2 * 2 * T * 32 * (192 + 192 + 128 + 128)}
    assignments = T * 8 * 8 / 256
    assert work["MoE"]["flops"] == 4 * 3 * 2 * (
        T * 256 * H + assignments * 3 * H * 1024)


def test_the_cell_asks_for_the_traffic_the_issue_gives():
    bench, cell, entry, cfg, traffic = hx.find_cell(CELL)
    assert cell["chips"] == 1 and traffic["driver"] == "bucketing_fit"
    assert cell["config"] == NAME and entry["reduced"] == CUT
    assert entry["source"] == cfg["source"]
    assert cell["traffic"] == traffic["name"] == "packed-4k-uniform-b1"
    assert traffic["length_mean"] == T and cfg["buckets"] == [T]
    assert traffic["batch_size"] == 1 and traffic["zipf_a"] == 0.0
    assert traffic["reference_check"] == {"batch": 1, "seq_len": T}
    assert traffic["env"] == {"MXNET_BACKWARD_DO_MIRROR": "1"}
    assert NAME in [c["name"] for c in bench["configs"]]
    reported = hx.metrics_of(bench, CELL, "per_layer")
    for name in ("linear_attention.channel_gated_layers_per_step.seq",
                 "linear_attention.layers_per_step.seq",
                 "linear_attention.chunks_per_step.seq",
                 "linear_attention.kernel_layers_per_step.seq",
                 "linear_attention.scan_kernel_layers_per_step.seq",
                 "linear_attention_roofline.seq",
                 "conv.kernel_layers_per_step.seq",
                 "causal_conv_roofline.seq",
                 "attention.latent_layers_per_step.seq",
                 "attention.lanes_per_pair.seq",
                 "attention.kernel_layers_per_step.seq",
                 "attention_roofline.seq", "moe_roofline.seq",
                 "moe.local_experts_per_step.seq",
                 "step.kept_residual_nodes_per_step.seq",
                 "compile.window_compiles.seq", "device.peak_hbm_gib.seq",
                 "kernels.mfu_pct.seq"):
        assert name in reported, name
    # every metric the kanana2-30b cell lists is reported here too
    assert set(hx.metrics_of(bench, "kanana2-30b-train-1c", "per_layer")) \
        <= set(reported)
    assert "attention.window_layers_per_step.seq" not in reported
    assert "moe.graph_routed_layers_per_step.seq" not in reported
    assert set(hx.metrics_of(bench, CELL, "end_to_end")) == {
        "train_tokens_per_s", "setup_s"}
    # the metric this configuration brought is its cell's
    new = [m for m in bench["per_layer"] if m["name"]
           == "linear_attention.channel_gated_layers_per_step.seq"]
    assert len(new) == 1 and CELL in new[0]["workloads"]
    assert new[0]["moves"] == "train_tokens_per_s"
    assert new[0]["better"] == "higher"
    assert new[0]["source"] == "program_counter"


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
    # the reference and the builder agree at the small size; a bfloat16
    # trunk at 64 features is off by more than TOLERANCES, which are set at
    # published widths on the chip
    assert run["correct"] or dtype == "bfloat16"
    assert run["failed"] == 0
    assert set(run["end_to_end"]) == {"train_tokens_per_s", "setup_s"}
    run["cell"] = dict(run["cell"], name=CELL)  # setup.* read their cell
    readers = hx.layer_readers()
    got = {n: readers[n].read(run)
           for n in hx.metrics_of(bench, CELL, "per_layer")}
    assert got["linear_attention.layers_per_step.seq"] == 4.0
    assert got["linear_attention.channel_gated_layers_per_step.seq"] == 4.0
    assert got["linear_attention.chunks_per_step.seq"] == 4.0
    assert got["linear_attention.kernel_layers_per_step.seq"] == 0.0
    assert got["linear_attention.scan_kernel_layers_per_step.seq"] == 0.0
    assert got["conv.kernel_layers_per_step.seq"] == 0.0        # the CPU
    assert got["attention.layers_per_step.seq"] == 1.0
    assert got["attention.latent_layers_per_step.seq"] == 1.0
    assert got["attention.lanes_per_pair.seq"] == 24 + 8 + 16
    assert got["attention.kernel_layers_per_step.seq"] == 0.0   # the CPU
    assert got["moe.layers_per_step.seq"] == 4.0
    assert got["moe.local_experts_per_step.seq"] == 4 * 4
    assert got["moe.assignments_per_step.seq"] == 4 * 16 * 3
    assert got["moe.kernel_matmuls_per_step.seq"] == 0.0        # the CPU
    # the latent layer's attention and four expert layers keep residuals;
    # the jax.numpy rule names none
    assert got["step.kept_residual_nodes_per_step.seq"] == 5.0
    assert got["dispatch.bucket_switches_per_step.seq"] == 0.0
    # (the canned trace has no ``CausalConv1D`` row: its share has nothing
    # to divide by here)
    missing = [n for n, v in got.items() if v is None]
    assert missing == ["causal_conv_roofline.seq"], missing
    # a program without the counter (the parent): 0, and no error
    run["obs"]["tm0"] = run["obs"]["tm1"] = {}
    assert readers[
        "linear_attention.channel_gated_layers_per_step.seq"].read(run) == 0
