"""``qwen3-next-80b-a3b``: its catalog keys kept but for the cut, its
parameter count and model FLOPs pinned to the arithmetic of its layers, and
the configuration at a tiny size through the ``bucketing_fit`` driver here
on the CPU (control flow and counts only: nothing timed here is a device
number)."""

import math

import pytest

from benchmark.lib import harness as hx
from benchmark.tests.test_drivers import canned_trace  # noqa: F401
from benchmark.tests.util import run_driver

NAME, CELL = "qwen3-next-80b-a3b", "qwen3-next-train-1c"
# the catalog's `config` of Qwen3-Next-80B-A3B-Instruct, as published
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
CUT = ["num_hidden_layers", "num_experts", "vocab_size"]
T = 8192


def published():
    return hx.load_json(hx.HERE, "configs", NAME + ".json")


def tiny():
    cfg = published()
    cfg.update(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
               linear_key_head_dim=8, linear_value_head_dim=8,
               moe_intermediate_size=16, shared_expert_intermediate_size=16,
               num_experts_published=16, num_experts=4, expert_offset=4,
               num_experts_per_tok=4, vocab_size=64, buckets=[128],
               compute_dtype="float32")
    _, cell, _, _, traffic = hx.find_cell(CELL)
    traffic.update(length_mean=128, length_std=1, batches_per_cycle=4,
                   min_slices=3, trace_steps=4,
                   reference_check={"batch": 1, "seq_len": 128})
    return cfg, traffic


def test_only_depth_experts_held_and_vocabulary_are_cut():
    cfg = published()
    assert cfg["reduced"] == CUT
    assert {k for k, v in PUBLISHED.items() if cfg[k] != v} == set(CUT)
    assert not [k for k in CUT if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    # one whole period: published layers 0-3, linear x 3 then full
    assert cfg["num_hidden_layers"] == cfg["full_attention_interval"] == 4
    assert cfg["num_hidden_layers_published"] == 48
    # the floors: 8 routed experts a layer, an eighth of the vocabulary
    assert cfg["num_experts"] == 16 and cfg["num_experts_published"] == 512
    assert cfg["expert_offset"] == 0
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"] == 151936
    assert cfg["optimizer"]["learning_rate"] == 1e-6
    assert cfg["router_aux_loss_coef"] == 0.001
    assert "32 chips share each layer" in cfg["deployment"]
    for key in ("norm_gains", "conv", "decay_and_write", "qk_l2norm",
                "gated_norm", "attention_gate", "qk_norm",
                "shared_expert_gate", "linear_attention_init", "mtp"):
        assert key in cfg["assumed"], key


def test_parameters_and_model_flops_are_the_arithmetic_of_the_layers():
    cfg = published()
    builder = hx.config_module("configs", NAME)
    h = 2048
    linear = h * 12288 + h * 64 + 8192 * 4 + 4096 * h + 32 + 32 + 128
    assert linear == 33718464
    attention = h * 8192 + 2 * h * 512 + 4096 * h + 2 * 256
    assert attention == 27263488
    expert = 3 * h * 512
    sparse = 512 * h + expert + h + 16 * expert
    assert sparse == 54528000
    period = 3 * (linear + sparse + 2 * h) + attention + sparse + 2 * h
    assert period == 346547264
    params = period + 2 * 18992 * h + h
    assert cfg["parameters"] == params == 424340544   # 6.79 GB at 16 B
    recurrence = 3 * 32 * 128 * 128                   # read, write, query
    linear_macs = h * 12288 + h * 64 + 4096 * h + 4 * 8192 + recurrence
    full_macs = h * 8192 + 2 * h * 512 + 4096 * h + 2 * (T / 2) * 16 * 256
    sparse_macs = 512 * h + h + expert + 10 * 16 / 512 * expert
    macs = 3 * linear_macs + full_macs + 4 * sparse_macs + h * 18992
    assert builder.forward_macs_per_token(cfg) == macs
    assert builder.train_flops_per_unit(cfg) == 3 * 2 * macs  # 1.36 GFLOP
    shares = [round(100 * x / macs) for x in (
        3 * (linear_macs + sparse_macs), full_macs + sparse_macs,
        h * 18992, 3 * recurrence)]
    assert shares == [54, 29, 17, 2]


def test_seeded_decays_remember_from_one_token_to_a_thousand():
    builder = hx.config_module("configs", NAME)
    kind, scale, offset = builder.init_rule("l0_A_log", (32, 1))
    assert kind == "uniform01" and offset == 0.0
    assert math.exp(scale) == pytest.approx(16.0)
    kind, scale, offset = builder.init_rule("l2_dt_bias", (32, 1))
    assert kind == "uniform01"
    assert math.exp(offset) == pytest.approx(0.001)
    assert math.exp(offset + scale) == pytest.approx(0.1)
    assert builder.init_rule("l0_out_norm_gamma", (128,)) == (
        "normal", 0.1, 1.0)
    assert builder.init_rule("l0_conv_weight", (8192, 4)) == (
        "normal", 0.02, 0.0)


def test_the_cell_asks_for_the_traffic_the_issue_gives():
    bench, cell, entry, cfg, traffic = hx.find_cell(CELL)
    assert cell["chips"] == 1 and traffic["driver"] == "bucketing_fit"
    assert cell["config"] == NAME and entry["reduced"] == CUT
    assert cell["traffic"] == traffic["name"]
    assert {k: traffic[k] for k in (
        "batch_size", "length_std", "zipf_a", "batches_per_cycle",
        "cycles_per_slice", "min_slices", "trace_steps", "kvstore")} == {
        "batch_size": 1, "length_std": 1, "zipf_a": 0.0,
        "batches_per_cycle": 8, "cycles_per_slice": 1, "min_slices": 10,
        "trace_steps": 8, "kvstore": "device"}
    # rows of 8192, or the issue's pre-declared fallback to the accepted
    # 4096-token traffic: one bucket, the check at the timed length
    assert cell["traffic"] in ("packed-8k-uniform-b1", "packed-4k-uniform-b1")
    rows = 8192 if cell["traffic"] == "packed-8k-uniform-b1" else 4096
    assert traffic["length_mean"] == rows and cfg["buckets"] == [rows]
    assert traffic["reference_check"] == {"batch": 1, "seq_len": rows}
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        "resnet50-train-4c"]
    reported = hx.metrics_of(bench, CELL, "per_layer")
    assert "linear_attention.layers_per_step.seq" in reported
    assert "linear_attention.chunks_per_step.seq" in reported
    assert "attention.kernel_layers_per_step.seq" in reported
    assert "attention.window_layers_per_step.seq" not in reported
    assert "step.stacked_wgrad_per_step.seq" not in reported


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bucketing_driver_runs_the_configuration(canned_trace, monkeypatch,  # noqa: F811
                                                 dtype):
    cfg, traffic = tiny()
    cfg["compute_dtype"] = dtype
    # as benchmark/run.py does: the cell's recomputation switch
    assert traffic["env"] == {"MXNET_BACKWARD_DO_MIRROR": "1"}
    for var, val in traffic["env"].items():
        monkeypatch.setenv(var, val)
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    run = run_driver(cfg, traffic, builder_of=NAME, seconds=0.3, trace=1,
                     bench=bench)
    tokens_a_step = run["obs"]["units"] / run["obs"]["steps"]
    assert 125 <= tokens_a_step <= 128         # a row of 125-128 real tokens
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
    assert got["linear_attention.layers_per_step.seq"] == 3.0
    assert got["linear_attention.chunks_per_step.seq"] == 3 * 128 / 64
    assert got["moe.layers_per_step.seq"] == 4.0
    assert got["moe.local_experts_per_step.seq"] == 4 * 4
    assert got["moe.assignments_per_step.seq"] == 4 * 128 * 4
    assert got["moe.kernel_matmuls_per_step.seq"] == 0.0   # the CPU
    assert got["attention.layers_per_step.seq"] == 1.0
    assert got["attention.kernel_layers_per_step.seq"] == 0.0
    # T 128 is one block of queries: each head scores the whole square
    assert got["attention.scored_pairs_per_step.seq"] == 4 * 128 * 128
    assert got["dispatch.bucket_switches_per_step.seq"] == 0.0
    missing = [n for n, v in got.items() if v is None]
    assert not missing, missing
    # a program with no such counter: 0, and no error
    run["obs"]["tm0"] = run["obs"]["tm1"] = {}
    assert readers["linear_attention.chunks_per_step.seq"].read(run) == 0
