"""``keye-vl-2.0-30b-a3b``: its catalog keys kept but for the cut, its
parameter count, model FLOPs and operators' least work pinned to the
arithmetic of its layers, and the configuration at a tiny size through the
``bucketing_fit`` driver here on the CPU (control flow and counts only:
nothing timed here is a device number). Every check of ``BENCHMARK.json``
asserts membership, never a length or a position of a list."""

import pytest

from benchmark.lib import harness as hx
from benchmark.tests.test_drivers import canned_trace  # noqa: F401
from benchmark.tests.util import run_driver

NAME, CELL = "keye-vl-2.0-30b-a3b", "keye-vl2-30b-train-1c"
# the catalog's `config` of Keye-VL-2.0-30B-A3B, as published
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
CUT = ["num_hidden_layers", "num_experts", "vocab_size"]
T = 16384
H = 2048
KEPT = 2048 * 2049 // 2 + (T - 2048) * 2048     # pairs a head keeps
SCORED = T * (T + 1) // 2                       # pairs an indexer head scores


def published():
    return hx.load_json(hx.HERE, "configs", NAME + ".json")


def tiny():
    cfg = published()
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, moe_intermediate_size=16,
               num_experts_published=16, num_experts=4, expert_offset=4,
               num_experts_per_tok=3, vocab_size=64, buckets=[32],
               compute_dtype="float32", num_hidden_layers=2,
               # 8 index heads: an exact 0 on a pair in 2^8, so no row is tied
               # at its threshold (at 2 heads many are, and the program keeps
               # every tied key where the reference keeps the lower positions)
               sa_config=dict(cfg["sa_config"], indexer_num_heads=8,
                              indexer_head_dim=8, topk=8))
    _, cell, _, _, traffic = hx.find_cell(CELL)
    traffic.update(length_mean=32, length_std=1, batches_per_cycle=4,
                   min_slices=3, trace_steps=4,
                   reference_check={"batch": 1, "seq_len": 32})
    return cfg, traffic


def test_only_depth_experts_held_and_vocabulary_are_cut():
    cfg = published()
    assert cfg["reduced"] == CUT
    assert {k for k, v in PUBLISHED.items() if cfg[k] != v} == set(CUT)
    assert set(PUBLISHED) <= set(cfg)
    # no width among them, and the nested groups are whole
    assert not [k for k in CUT if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    assert cfg["sa_config"] == PUBLISHED["sa_config"]
    assert cfg["num_hidden_layers"] == 4
    assert cfg["num_hidden_layers_published"] == 48
    # the floors: four layers (the period is one), 8 routed experts a layer,
    # an eighth of the vocabulary
    assert cfg["num_experts"] == 8 and cfg["num_experts_published"] == 128
    assert cfg["expert_offset"] == 0
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"] == 151936
    assert cfg["optimizer"]["learning_rate"] == 1e-6
    assert cfg["index_loss_coef"] == 1.0
    assert cfg["router_aux_loss_coef"] == 0.001
    assert "16 chips share each layer" in cfg["deployment"]
    for key in ("vision_tower", "positions", "qk_norm", "indexer",
                "indexer_quantisation", "sa_chunks", "index_loss_coef",
                "index_norm_eps", "router", "router_aux_loss_coef",
                "parameters", "optimizer", "init", "batch", "precision"):
        assert key in cfg["assumed"], key
    # the seeded weights: a unit embedding keeps the tokens apart through the
    # layers, so every seed routes alike (init_rule says why)
    rule = hx.config_module("configs", NAME).init_rule
    assert rule("embed_weight", (18992, 2048)) == ("normal", 1.0, 0.0)
    assert rule("pred_weight", (18992, 2048)) == ("normal", 0.02, 0.0)
    assert rule("l0_moe_router_weight", (128, 2048)) == ("normal", 0.02, 0.0)
    assert rule("l0_input_norm_gamma", (2048,)) == ("normal", 0.1, 1.0)
    assert "embedding normal(0, 1)" in cfg["assumed"]["init"]


def test_parameters_and_model_flops_are_the_arithmetic_of_the_layers():
    import mxnet_tpu as mx
    import numpy as np

    cfg = published()
    builder = hx.config_module("configs", NAME)
    attention = 2 * H * 32 * 128 + 2 * H * 4 * 128 + 2 * 128
    indexer = H * 16 * 64 + H * 64 + 2 * 64 + H * 16
    assert (attention, indexer) == (18874624, 2261120)
    experts = 8 * 3 * H * 768
    layer = attention + indexer + 128 * H + experts + 2 * H
    assert layer == 59150720
    params = 4 * layer + 2 * 18992 * H + H
    assert cfg["parameters"] == params == 314396160     # 5.03 GB at 16 B
    # and infer_shape says the same
    sym = builder.sym_gen(cfg, mx)[0](T)[0]
    arg_shapes, _, _ = sym.infer_shape(data=(1, T), softmax_label=(1, T))
    assert params == sum(
        int(np.prod(s)) for n, s in zip(sym.list_arguments(), arg_shapes)
        if n not in ("data", "softmax_label"))
    assert builder.kept_pairs(T, 2048) == KEPT == 31458304
    assert builder.index_pairs(T) == SCORED == 134225920
    projections = 2 * H * 4096 + 2 * H * 512 + H * (1024 + 64 + 16)
    macs = 4 * (projections + 2 * 32 * 128 * KEPT / T + 16 * 64 * SCORED / T
                + 128 * H + 8 * 8 / 128 * 3 * H * 768) + H * 18992
    assert builder.forward_macs_per_token(cfg) == macs
    assert builder.train_flops_per_unit(cfg) == 3 * 2 * macs
    # a step of 16 384: 22.6 TFLOP; the kept pairs and the indexer's are
    # 42% of it (a masked triangle would be 2.2 TFLOP a layer forward)
    assert round(builder.train_flops_per_unit(cfg) * T / 1e12, 1) == 22.6
    sparse = 4 * (2 * 32 * 128 * KEPT + 16 * 64 * SCORED)
    assert round(100 * sparse / (macs * T)) == 42


def test_operator_work_counts_the_kept_pairs_and_the_indexers():
    """``RingAttention``: the pairs the queries KEEP at both widths and the
    pairs the indexer scores, each product once forward and twice backward,
    each operand once across HBM with its gradient, never the masked
    triangle; ``MoE`` as the other builders."""
    from benchmark.lib import flops

    cfg = published()
    builder = hx.config_module("configs", NAME)
    traffic = hx.load_json(hx.HERE, "traffic", "packed-16k-uniform-b1.json")
    work = builder.operator_work(cfg, traffic)
    assert set(work) == {"MoE", "RingAttention"}
    assert work["RingAttention"] == {
        "flops": 4 * 3 * 2 * (32 * KEPT * (128 + 128) + 16 * SCORED * 64),
        "bytes": 4 * 2 * 2 * T * (32 * 256 + 4 * 256 + 16 * 64 + 64 + 16)}
    triangle = flops.attention_work(1, T, 32, 4, 128, 128)["flops"]
    assert 4 * triangle > 1.9 * work["RingAttention"]["flops"]
    assignments = T * 8 * 8 / 128
    assert work["MoE"]["flops"] == 4 * 3 * 2 * (
        T * 128 * H + assignments * 3 * H * 768)


def test_the_cell_asks_for_the_traffic_the_issue_gives():
    bench, cell, entry, cfg, traffic = hx.find_cell(CELL)
    assert cell["chips"] == 1 and traffic["driver"] == "bucketing_fit"
    assert cell["config"] == NAME and entry["reduced"] == CUT
    assert entry["source"] == cfg["source"]
    assert cell["traffic"] == traffic["name"] == "packed-16k-uniform-b1"
    assert traffic["length_mean"] == T and cfg["buckets"] == [T]
    assert traffic["batch_size"] == 1 and traffic["zipf_a"] == 0.0
    assert traffic["length_std"] == 1 and traffic["batches_per_cycle"] == 4
    assert traffic["cycles_per_slice"] == 1 and traffic["min_slices"] == 10
    assert traffic["trace_steps"] == 4 and traffic["kvstore"] == "device"
    assert traffic["reference_check"] == {"batch": 1, "seq_len": T}
    assert traffic["env"] == {"MXNET_BACKWARD_DO_MIRROR": "1"}
    assert NAME in [c["name"] for c in bench["configs"]]
    reported = hx.metrics_of(bench, CELL, "per_layer")
    for name in ("attention.selected_layers_per_step.seq",
                 "attention.selected_pairs_per_step.seq",
                 "attention.index_pairs_per_step.seq",
                 "attention.layers_per_step.seq",
                 "attention.kernel_layers_per_step.seq",
                 "attention.scored_pairs_per_step.seq",
                 "attention_roofline.seq", "moe_roofline.seq",
                 "moe.layers_per_step.seq", "moe.assignments_per_step.seq",
                 "moe.kernel_matmuls_per_step.seq",
                 "moe.local_experts_per_step.seq",
                 "step.kept_residual_nodes_per_step.seq",
                 "compile.window_compiles.seq", "device.peak_hbm_gib.seq",
                 "device.idle_pct.seq", "kernels.mfu_pct.seq"):
        assert name in reported, name
    assert "attention.window_layers_per_step.seq" not in reported
    assert "attention.latent_layers_per_step.seq" not in reported
    assert "linear_attention.layers_per_step.seq" not in reported
    assert set(hx.metrics_of(bench, CELL, "end_to_end")) == {
        "train_tokens_per_s", "setup_s"}
    # the metrics this configuration brought are its cell's
    for name, better in (("attention.selected_layers_per_step.seq", "higher"),
                         ("attention.selected_pairs_per_step.seq", "lower"),
                         ("attention.index_pairs_per_step.seq", "lower")):
        new = [m for m in bench["per_layer"] if m["name"] == name]
        assert len(new) == 1 and CELL in new[0]["workloads"]
        assert new[0]["moves"] == "train_tokens_per_s"
        assert new[0]["better"] == better
        assert new[0]["source"] == "program_counter"


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bucketing_driver_runs_the_configuration(canned_trace, dtype):  # noqa: F811
    cfg, traffic = tiny()
    cfg["compute_dtype"] = dtype
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    run = run_driver(cfg, traffic, builder_of=NAME, seconds=0.3, trace=1,
                     bench=bench)
    tokens_a_step = run["obs"]["units"] / run["obs"]["steps"]
    assert 29 <= tokens_a_step <= 32           # a row of 29-32 real tokens
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
    kept = sum(min(t + 1, 8) for t in range(32))
    assert got["attention.layers_per_step.seq"] == 2.0
    assert got["attention.selected_layers_per_step.seq"] == 2.0
    assert got["attention.selected_pairs_per_step.seq"] == 2 * 4 * kept
    assert got["attention.index_pairs_per_step.seq"] == 2 * 8 * 32 * 33 // 2
    assert got["attention.scored_pairs_per_step.seq"] == 2 * 4 * 32 * 32
    assert got["attention.kernel_layers_per_step.seq"] == 0.0
    assert got["moe.layers_per_step.seq"] == 2.0
    assert got["moe.local_experts_per_step.seq"] == 2 * 4
    assert got["moe.assignments_per_step.seq"] == 2 * 32 * 3
    assert got["moe.kernel_matmuls_per_step.seq"] == 0.0        # the CPU
    # both layers' attention and mixture keep residuals
    assert got["step.kept_residual_nodes_per_step.seq"] == 4.0
    assert got["dispatch.bucket_switches_per_step.seq"] == 0.0
    missing = [n for n, v in got.items() if v is None]
    assert missing == [], missing
    # a program without the counters (the parent): 0, and no error
    run["obs"]["tm0"] = run["obs"]["tm1"] = {}
    for name in ("attention.selected_layers_per_step.seq",
                 "attention.selected_pairs_per_step.seq",
                 "attention.index_pairs_per_step.seq"):
        assert readers[name].read(run) == 0
