"""``kanana-2-30b-a3b``: its catalog keys kept but for the cut, its parameter
count, model FLOPs and the attention kernels' roofline arithmetic pinned to
the arithmetic of its layers, and the configuration at a tiny size through
the ``bucketing_fit`` driver here on the CPU (control flow and counts only:
nothing timed here is a device number)."""

import pytest

from benchmark.lib import harness as hx
from benchmark.tests.test_drivers import canned_trace  # noqa: F401
from benchmark.tests.util import run_driver

NAME, CELL = "kanana-2-30b-a3b", "kanana2-30b-train-1c"
# the catalog's `config` of kanana-2-30b-a3b-instruct-2601, as published
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256}
CUT = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
T = 8192


def published():
    return hx.load_json(hx.HERE, "configs", NAME + ".json")


def tiny():
    cfg = published()
    cfg.update(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=24,
               qk_rope_head_dim=8, qk_head_dim=32, v_head_dim=16,
               kv_lora_rank=32, intermediate_size=96,
               moe_intermediate_size=16, n_routed_experts_published=16,
               n_routed_experts=4, expert_offset=4, num_experts_per_tok=3,
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
    # the leading dense layer and four of the expert layers that follow
    assert cfg["num_hidden_layers"] == 5 and cfg["first_k_dense_replace"] == 1
    assert cfg["num_hidden_layers_published"] == 48
    # the floors: 8 routed experts a layer, an eighth of the vocabulary
    assert cfg["n_routed_experts"] == 8
    assert cfg["n_routed_experts_published"] == 128
    assert cfg["expert_offset"] == 0
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"] == 128256
    assert cfg["optimizer"]["learning_rate"] == 1e-6
    assert "16 chips share each layer" in cfg["deployment"]
    for key in ("latent", "softmax_scale", "positions", "shared_experts",
                "router", "expert_bias", "head_dim", "q_lora_rank"):
        assert key in cfg["assumed"], key


def test_parameters_and_model_flops_are_the_arithmetic_of_the_layers():
    cfg = published()
    builder = hx.config_module("configs", NAME)
    h = 2048
    q, kv_a = h * 32 * 192, h * (512 + 64)
    kv_b, o = 512 * 32 * (128 + 128), 32 * 128 * h
    assert (q, kv_a, kv_b, o) == (12582912, 1179648, 4194304, 8388608)
    mixer = q + kv_a + 512 + kv_b + o
    assert mixer == 26345984
    expert, shared, router = 3 * h * 768, 3 * h * 1536, 128 * h
    expert_layer = mixer + router + 128 + shared + 8 * expert + 2 * h
    assert expert_layer == 73798272           # 128: the selection bias
    dense_layer = mixer + 3 * h * 6144 + 2 * h
    assert dense_layer == 64098816
    params = dense_layer + 4 * expert_layer + 2 * 16032 * h + h
    assert cfg["parameters"] == params == 424961024    # 6.80 GB at 16 B
    projections = mixer - 512
    assert builder.mixer_macs_per_token(cfg) == projections
    scores = 32 * (T // 2) * (192 + 128)      # p.v at 128, not at 192
    assert builder.score_macs_per_token(cfg) == scores == 41943040
    macs = (5 * (projections + scores) + 3 * h * 6144
            + 4 * (shared + router + 6 * 8 / 128 * expert) + h * 16032)
    assert builder.forward_macs_per_token(cfg) == macs
    assert builder.train_flops_per_unit(cfg) == 3 * 2 * macs  # 2.75 GFLOP
    shares = [round(100 * x / macs) for x in (
        5 * scores, 5 * (projections + scores), h * 16032)]
    assert shares == [46, 75, 7]
    # a step of 8192 tokens: 22.5 TFLOP
    assert round(builder.train_flops_per_unit(cfg) * T / 1e12, 1) == 22.5


def test_attention_kernel_roofline_arithmetic():
    """The operations and bytes of one layer's ``attention_fwd`` /
    ``attention_bwd`` launch at the tiles the rule gives the cell (512 x
    512): the visit list's pairs, two matmuls forward and five backward at
    their own widths."""
    cfg = published()
    builder = hx.config_module("configs", NAME)
    blocks = T // 512
    pairs = 512 * 512 * blocks * (blocks + 1) // 2
    assert builder.visited_pairs(T, 512, 512) == pairs == 35651584
    flops = builder.attention_kernel_flops(cfg)
    assert flops == {"attention_fwd": 2 * 32 * pairs * (192 + 128),
                     "attention_bwd": 2 * 32 * pairs * (3 * 192 + 2 * 128)}
    moved = builder.attention_kernel_bytes(cfg)
    rows = 32 * T
    assert moved == {
        "attention_fwd": rows * (2 * (192 + 192 + 128 + 128) + 4),
        "attention_bwd": rows * (2 * (4 * 192 + 3 * 128) + 8)}
    # compute-bound by three orders: 0.73 TFLOP over 0.34 GB forward
    assert flops["attention_fwd"] / moved["attention_fwd"] > 2000
    # the program's own visit list agrees
    from mxnet_tpu.ops import flash_attention as fa

    assert fa.scored_pairs(T, 512, 512, True) == pairs


def test_the_cell_asks_for_the_traffic_the_issue_gives():
    bench, cell, entry, cfg, traffic = hx.find_cell(CELL)
    assert cell["chips"] == 1 and traffic["driver"] == "bucketing_fit"
    assert cell["config"] == NAME and entry["reduced"] == CUT
    assert entry["source"] == cfg["source"]
    assert cell["traffic"] == traffic["name"] == "packed-8k-uniform-b1"
    assert traffic["length_mean"] == T and cfg["buckets"] == [T]
    assert traffic["reference_check"] == {"batch": 1, "seq_len": T}
    assert traffic["env"] == {"MXNET_BACKWARD_DO_MIRROR": "1"}
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        "resnet50-train-4c"]
    reported = hx.metrics_of(bench, CELL, "per_layer")
    for name in ("attention.latent_layers_per_step.seq",
                 "attention.lanes_per_pair.seq",
                 "attention.kernel_layers_per_step.seq",
                 "attention.scored_pairs_per_step.seq",
                 "moe.local_experts_per_step.seq",
                 "step.kept_residual_nodes_per_step.seq",
                 "kernels.mfu_pct.seq"):
        assert name in reported, name
    assert "attention.window_layers_per_step.seq" not in reported
    assert "linear_attention.layers_per_step.seq" not in reported
    assert hx.metrics_of(bench, CELL, "end_to_end") == [
        "train_tokens_per_s", "setup_s"]
    # the two metrics this configuration brought are its cell's alone
    for m in bench["per_layer"]:
        if m["name"] in ("attention.latent_layers_per_step.seq",
                         "attention.lanes_per_pair.seq"):
            assert m["workloads"] == [CELL]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bucketing_driver_runs_the_configuration(canned_trace, monkeypatch,  # noqa: F811
                                                 dtype):
    cfg, traffic = tiny()
    cfg["compute_dtype"] = dtype
    # as benchmark/run.py does: the cell's recomputation switch
    for var, val in traffic["env"].items():
        monkeypatch.setenv(var, val)
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
    assert got["attention.layers_per_step.seq"] == 5.0
    assert got["attention.latent_layers_per_step.seq"] == 5.0
    assert got["attention.lanes_per_pair.seq"] == 24 + 8 + 16
    assert got["attention.kernel_layers_per_step.seq"] == 0.0   # the CPU
    # T 16 is one block of queries: each head scores the whole square
    assert got["attention.scored_pairs_per_step.seq"] == 5 * 4 * 16 * 16
    assert got["moe.layers_per_step.seq"] == 4.0
    assert got["moe.local_experts_per_step.seq"] == 4 * 4
    assert got["moe.assignments_per_step.seq"] == 4 * 16 * 3
    assert got["moe.kernel_matmuls_per_step.seq"] == 0.0   # the CPU
    # five attention nodes and four expert layers keep their residuals
    assert got["step.kept_residual_nodes_per_step.seq"] == 9.0
    assert got["dispatch.bucket_switches_per_step.seq"] == 0.0
    missing = [n for n, v in got.items() if v is None]
    assert not missing, missing
    # a program with neither counter (the parent): 0, and no error
    run["obs"]["tm0"] = run["obs"]["tm1"] = {}
    assert readers["attention.latent_layers_per_step.seq"].read(run) == 0
    assert readers["attention.lanes_per_pair.seq"].read(run) == 0
