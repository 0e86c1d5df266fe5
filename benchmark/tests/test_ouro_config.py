"""``ouro-2.6b``: its catalog keys kept but for the depth, its parameter
count, model FLOPs and operators' least work pinned to the arithmetic of a
stack that runs ``total_ut_steps`` times, and the configuration at a tiny size
through the ``bucketing_fit`` driver here on the CPU (control flow and counts
only: nothing timed here is a device number). Every check of
``BENCHMARK.json`` asserts membership, never a length or a position of a
list."""

import pytest

from benchmark.lib import harness as hx
from benchmark.tests.test_drivers import canned_trace  # noqa: F401
from benchmark.tests.util import run_driver

NAME, CELL = "ouro-2.6b", "ouro-2.6b-train-1c"
# the catalog's `config` of Ouro-2.6B, as published
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}
CUT = ["num_hidden_layers", "layer_types"]
NEW = ("loss.exit_heads_per_step.seq",
       "step.shared_weight_reads_per_step.seq", "exit_loss_roofline.seq")
T, H, F, V, R = 4096, 2048, 5632, 49152, 4


def published():
    return hx.load_json(hx.HERE, "configs", NAME + ".json")


def tiny():
    cfg = published()
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               head_dim=16, intermediate_size=96, vocab_size=64,
               buckets=[32], compute_dtype="float32", num_hidden_layers=2,
               layer_types=["full_attention"] * 2)
    _, cell, _, _, traffic = hx.find_cell(CELL)
    traffic.update(length_mean=32, length_std=1, batches_per_cycle=4,
                   min_slices=3, trace_steps=4,
                   reference_check={"batch": 1, "seq_len": 32})
    return cfg, traffic


def test_only_the_depth_is_cut():
    cfg = published()
    assert cfg["reduced"] == CUT
    assert {k for k, v in PUBLISHED.items() if cfg[k] != v} == set(CUT)
    assert set(PUBLISHED) <= set(cfg)
    # no width among them, no head count, not the vocabulary, not the loop
    assert not [k for k in CUT if k.endswith(("_dim", "_rank", "_size"))]
    layers = cfg["num_hidden_layers"]
    assert layers in (4, 6)                 # the issue's N, or its fallback
    assert cfg["layer_types"] == ["full_attention"] * layers
    assert cfg["num_hidden_layers_published"] == 48
    assert cfg["optimizer"]["learning_rate"] == 1e-6
    assert cfg["exit_entropy_beta"] == 0.1
    assert "pipeline" in cfg["deployment"]
    for key in ("num_hidden_layers", "layer_types", "exit_entropy_beta",
                "sandwich_norms", "carried_stream", "exit_gate", "objective",
                "positions", "parameters", "optimizer", "weight_decay",
                "clip_gradient", "init", "loss_normalisation", "batch",
                "precision"):
        assert key in cfg["assumed"], key
    rule = hx.config_module("configs", NAME).init_rule
    assert rule("early_exit_gate_bias", (1,)) == ("const", -1.0, 0.0)
    assert rule("early_exit_gate_weight", (1, H)) == ("normal", 0.02, 0.0)
    assert rule("pred_weight", (V, H)) == ("normal", 0.02, 0.0)
    assert rule("final_norm_gamma", (H,)) == ("normal", 0.1, 1.0)


def test_parameters_and_model_flops_are_the_arithmetic_of_the_loop():
    import mxnet_tpu as mx
    import numpy as np

    cfg = published()
    layers = cfg["num_hidden_layers"]
    builder = hx.config_module("configs", NAME)
    layer = 4 * H * H + 3 * H * F
    assert layer == 51380224
    params = layers * layer + 2 * V * H + (H + 1) + (4 * layers + 1) * H
    assert cfg["parameters"] == params == {4: 406884353,
                                           6: 509661185}[layers]
    sym = builder.sym_gen(cfg, mx)[0](T)[0]
    arg_shapes, _, _ = sym.infer_shape(data=(1, T), softmax_label=(1, T))
    assert params == sum(
        int(np.prod(s)) for n, s in zip(sym.list_arguments(), arg_shapes)
        if n not in ("data", "softmax_label"))
    # a layer counts once a pass and the head once an exit
    application = layer + 2 * ((T + 1) / 2) * 16 * 128
    macs = R * (layers * application + H * V)
    assert builder.layer_macs_per_token(cfg) == application
    assert builder.forward_macs_per_token(cfg) == macs
    assert builder.train_flops_per_unit(cfg) == 3 * 2 * macs
    # forward a token: 2 x 51.4 M x 4 N in the layers, 0.81 GFLOP in the
    # four heads (ISSUE 55's arithmetic); a step of 4096: 33.4 / 45.1 TFLOP
    assert round(2 * R * H * V / 1e9, 2) == 0.81
    assert round(builder.train_flops_per_unit(cfg) * T / 1e12, 1) == {
        4: 33.4, 6: 45.1}[layers]


def test_operator_work_counts_every_application_and_every_exit():
    """``RingAttention``: the causal triangle's exact pairs, once a layer
    APPLICATION (R x layers), each product once forward and twice backward;
    ``ExitSoftmaxOutput``: every exit's logits read and their gradient
    written once, the one float32 output written once; bytes, no product."""
    from benchmark.lib import flops

    cfg = published()
    layers = cfg["num_hidden_layers"]
    builder = hx.config_module("configs", NAME)
    traffic = hx.load_json(hx.HERE, "traffic", "packed-4k-uniform-b1.json")
    work = builder.operator_work(cfg, traffic)
    assert set(work) == {"RingAttention", "ExitSoftmaxOutput"}
    one = flops.attention_work(1, T, 16, 16, 128, 128)
    assert one["flops"] == 3 * 2 * 16 * (T * (T + 1) // 2) * 256
    assert work["RingAttention"] == {"flops": R * layers * one["flops"],
                                     "bytes": R * layers * one["bytes"]}
    assert work["ExitSoftmaxOutput"] == {
        "flops": 0, "bytes": T * V * (R * 2 + 4 + R * 2)}
    # 3.75 GiB a step: 4.9 ms at the v5e's 819 GB/s
    assert round(work["ExitSoftmaxOutput"]["bytes"] / 2 ** 30, 2) == 3.75


def test_the_cell_asks_for_the_traffic_the_issue_gives():
    bench, cell, entry, cfg, traffic = hx.find_cell(CELL)
    assert cell["chips"] == 1 and traffic["driver"] == "bucketing_fit"
    assert cell["config"] == NAME and entry["reduced"] == CUT
    assert entry["source"] == cfg["source"]
    assert cell["traffic"] == traffic["name"] == "packed-4k-uniform-b1"
    assert traffic["length_mean"] == T and cfg["buckets"] == [T]
    assert traffic["batch_size"] == 1 and traffic["zipf_a"] == 0.0
    assert traffic["env"] == {"MXNET_BACKWARD_DO_MIRROR": "1"}
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert NAME in [c["name"] for c in bench["configs"]]
    reported = hx.metrics_of(bench, CELL, "per_layer")
    for name in NEW + (
            "attention.layers_per_step.seq",
            "attention.kernel_layers_per_step.seq", "attention_roofline.seq",
            "compile.window_compiles.seq", "device.peak_hbm_gib.seq",
            "device.idle_pct.seq", "kernels.mfu_pct.seq",
            "memory.step_kept_outputs_gib.seq", "memory.step_scratch_gib.seq",
            "setup.trace_lower_s", "setup.compile_or_load_s",
            "step.scoped_nodes_per_step.seq"):
        assert name in reported, name
    assert not [n for n in reported if n.startswith("moe")]
    assert "step.stacked_wgrad_per_step.seq" not in reported
    # every list that holds the OLMoE cell holds this one, but the moe* ones
    for m in bench["per_layer"]:
        if "olmoe-1b7b-train-1c" in m.get("workloads", ()):
            assert (CELL in m["workloads"]) != m["name"].startswith("moe"), \
                m["name"]
    assert set(hx.metrics_of(bench, CELL, "end_to_end")) == {
        "train_tokens_per_s", "setup_s"}
    # the metrics this configuration brought are its cell's
    for name in NEW:
        new = [m for m in bench["per_layer"] if m["name"] == name]
        assert len(new) == 1 and new[0]["workloads"] == [CELL]
        assert new[0]["moves"] == "train_tokens_per_s"
        assert new[0]["better"] == "higher"
        assert new[0]["source"] == (
            "device_trace" if "roofline" in name else "program_counter")


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
    assert got["loss.exit_heads_per_step.seq"] == 4.0
    assert got["step.shared_weight_reads_per_step.seq"] == R * (11 * 2 + 3)
    assert got["attention.layers_per_step.seq"] == R * 2
    assert got["attention.kernel_layers_per_step.seq"] == 0.0   # the CPU
    assert got["dispatch.bucket_switches_per_step.seq"] == 0.0
    assert got["attention_roofline.seq"] is not None
    # the canned table holds no row of the loss: nothing to read, no error
    missing = [n for n, v in got.items() if v is None]
    assert missing == ["exit_loss_roofline.seq"], missing
    trace = run["obs"]["trace"]
    run["obs"]["trace"] = dict(trace, table=dict(
        trace["table"], by_operator=trace["table"]["by_operator"] + [
            {"operator": "ExitSoftmaxOutput", "pass": "backward", "ms": 4.0,
             "calls": 2}]))
    share = readers["exit_loss_roofline.seq"].read(run)
    work = hx.config_module("configs", NAME).operator_work(cfg, traffic)
    steps = run["obs"]["trace_slice"][0]
    assert share == pytest.approx(
        100 * work["ExitSoftmaxOutput"]["bytes"]
        / run["obs"]["peak_bytes_per_s"] / (4.0e-3 / steps))
    # a program without the counters (the parent): 0, and no error
    run["obs"]["tm0"] = run["obs"]["tm1"] = {}
    for name in NEW[:2]:
        assert readers[name].read(run) == 0
