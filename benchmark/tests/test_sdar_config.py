"""``sdar-30b-a3b``: its catalog keys kept but for the depth, the experts held
and the vocabulary; its parameter count, model FLOPs and operators' least
work pinned to the arithmetic of a step that reads every row twice under the
block-diffusion mask; and the configuration at a tiny size through the
``bucketing_fit`` driver here on the CPU (control flow and counts only:
nothing timed here is a device number). Every check of ``BENCHMARK.json``
asserts membership, never a length or a position of a list."""

import numpy as np
import pytest

from benchmark.lib import harness as hx
from benchmark.tests.test_drivers import canned_trace  # noqa: F401
from benchmark.tests.util import run_driver

NAME, CELL = "sdar-30b-a3b", "sdar-30b-a3b-train-1c"
KEYE = "keye-vl2-30b-train-1c"
# the catalog's `config` of SDAR-30B-A3B-Chat, as published
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
CUT = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW = {"attention.diffusion_layers_per_step.seq": ("1/step", "higher"),
       "attention.scored_per_kept_pair.seq": ("ratio", "lower"),
       "step.trunk_rows_per_token.seq": ("rows/token", "lower")}
SELECTION = ("attention.selected_layers_per_step.seq",
             "attention.selected_pairs_per_step.seq",
             "attention.index_pairs_per_step.seq")
T, H, V, BD = 8192, 2048, 18992, 4


def published():
    return hx.load_json(hx.HERE, "configs", NAME + ".json")


def tiny():
    cfg = published()
    cfg.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
               head_dim=16, moe_intermediate_size=32, num_experts=4,
               num_experts_published=16, expert_offset=4,
               num_experts_per_tok=2, vocab_size=64, buckets=[32],
               compute_dtype="float32", num_hidden_layers=2)
    _, cell, _, _, traffic = hx.find_cell(CELL)
    traffic.update(length_mean=32, length_std=1, batches_per_cycle=4,
                   min_slices=3, trace_steps=4,
                   reference_check={"batch": 1, "seq_len": 32})
    return cfg, traffic


def test_only_the_depth_the_held_experts_and_the_vocabulary_are_cut():
    cfg = published()
    assert cfg["reduced"] == CUT
    assert {k for k, v in PUBLISHED.items() if cfg[k] != v} == set(CUT)
    assert set(PUBLISHED) <= set(cfg)
    # no width among them, no head count, not the experts a token
    assert not [k for k in CUT if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    assert cfg["num_hidden_layers"] == 4 and cfg["vocab_size"] == V
    assert cfg["num_experts"] in (16, 8)    # the issue's sizing, or its fallback
    assert cfg["num_hidden_layers_published"] == 48
    assert cfg["num_experts_published"] == 128
    assert cfg["vocab_size_published"] == 151936 == 8 * V
    assert cfg["block_length"] == BD and cfg["noise_eps"] == 1e-3
    assert cfg["router_aux_loss_coef"] == 0.001
    assert cfg["optimizer"] == {
        "name": "adam", "learning_rate": 1e-6, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8, "wd": 0.0}
    assert f"{128 // cfg['num_experts']} chips share each layer" \
        in cfg["deployment"]
    assert "GiB" in cfg["sizing"]           # which sizing, the measured peak
    for key in ("num_hidden_layers", "num_experts", "vocab_size",
                "parameters", "block_length", "noise_schedule", "mask_id",
                "pads", "two_copies", "objective", "positions", "qk_norm",
                "router", "router_aux_loss_coef", "optimizer", "weight_decay",
                "clip_gradient", "init", "loss_normalisation", "batch",
                "precision"):
        assert key in cfg["assumed"], key
    rule = hx.config_module("configs", NAME).init_rule
    assert rule("pred_weight", (V, H)) == ("normal", 0.02, 0.0)
    assert rule("l0_q_norm_gamma", (128,)) == ("normal", 0.1, 1.0)
    assert rule("l0_input_norm_gamma", (H,)) == ("normal", 0.1, 1.0)


def test_every_seed_sends_the_masked_rows_to_one_held_expert_a_layer():
    """The masked rows (a quarter of the trunk's) carry one vector and choose
    one set of 8 experts a layer; the leaves that decide it are not the
    run's seed's, so every seed does the same work: exactly one of the 8 is
    held here in every layer, by 0.7 of score on both sides."""
    import jax

    from benchmark.lib import gen

    builder = hx.config_module("configs", NAME)
    rule, held = builder.init_rule, published()["num_experts"]
    kind, scale, offset = rule("embed_weight", (V, H))
    assert kind == "normal" and scale.shape == offset.shape == (V, 1)
    assert (scale[:-1] == 1.0).all() and not offset[:-1].any()
    assert scale[-1] == 0.0 and offset[-1] == 1.0      # MASK: all ones
    assert len(builder.STILL_SEEDS) == published()["num_hidden_layers"]
    chosen = set()
    for layer in range(4):
        kind, router, _ = rule(f"l{layer}_moe_router_weight", (128, H))
        assert kind == "const" and router.shape == (128, H)
        assert abs(router.std() - 0.02) < 2e-4 and abs(router.mean()) < 1e-4
        kind, gain, _ = rule(f"l{layer}_post_norm_gamma", (H,))
        assert kind == "const" and abs(gain.std() - 0.1) < 0.01
        scores = router @ gain          # the normed MASK vector is ones
        order = np.argsort(-scores)
        inside = [e for e in order[:8] if e < held]
        assert len(inside) == 1
        assert scores[inside[0]] - scores[order[8]] > 0.7
        assert scores[order[7]] - max(scores[e] for e in order[8:]
                                      if e < held) > 0.7
        chosen.add(inside[0])
    assert len(chosen) == 4             # another held expert in every layer
    # through the harness's generator: those leaves alike on two seeds, the
    # others not; a small embedding's last row ones
    shapes = {"embed_weight": (64, 8), "l2_moe_router_weight": (16, 8),
              "l2_post_norm_gamma": (8,), "l2_q_weight": (8, 8)}
    specs = [(n, s, "float32") + tuple(rule(n, s))
             for n, s in shapes.items()]
    one, two = (gen.make_leaves(jax, seed, specs)
                for seed in (5700000011, 2957000012))
    for name in ("l2_moe_router_weight", "l2_post_norm_gamma"):
        assert (one[name] == two[name]).all() and one[name].std() > 0
    assert (one["embed_weight"][-1] == 1.0).all()
    assert (two["embed_weight"][-1] == 1.0).all()
    assert not (one["embed_weight"][:-1] == two["embed_weight"][:-1]).any()
    assert not (one["l2_q_weight"] == two["l2_q_weight"]).any()


def test_parameters_and_model_flops_are_the_arithmetic_of_two_rows_a_token():
    import mxnet_tpu as mx

    cfg = published()
    held = cfg["num_experts"]
    builder = hx.config_module("configs", NAME)
    attention = 2 * H * 4096 + 2 * H * 512 + 2 * 128
    assert attention == 18874624
    layer = attention + 128 * H + held * 3 * H * 768 + 2 * H
    params = 4 * layer + 2 * V * H + H
    assert cfg["parameters"] == params == {16: 456346624,
                                           8: 305351680}[held]
    sym = builder.sym_gen(cfg, mx)[0](T)[0]
    arg_shapes, _, _ = sym.infer_shape(data=(1, T), softmax_label=(1, T))
    assert params == sum(
        int(np.prod(s)) for n, s in zip(sym.list_arguments(), arg_shapes)
        if n not in ("data", "softmax_label"))
    # ISSUE 57's arithmetic, forward multiply-adds a clean token: the pairs
    # 4 x 32 x 256 x 8196, the projections of two trunk rows 4 x 37.7 M,
    # router and held experts, the head once
    pairs = 4 * 32 * 256 * (T + BD)
    projections = 4 * 2 * (attention - 256)
    mixture = 4 * 2 * (128 * H + 8 * held / 128 * 3 * H * 768)
    macs = pairs + projections + mixture + H * V
    assert round(pairs / 1e6, 1) == 268.6
    assert round(projections / 1e6, 1) == 151.0
    assert round(H * V / 1e6, 1) == 38.9
    assert builder.forward_macs_per_token(cfg) == pytest.approx(macs,
                                                                rel=1e-12)
    assert builder.train_flops_per_unit(cfg) == pytest.approx(6 * macs)
    if held == 16:
        assert round(mixture / 1e6, 1) == 39.8
        assert round(macs / 1e6) == 498
        assert round(6 * macs * T / 1e12, 1) == 24.5    # TFLOP a step


@pytest.mark.parametrize("length,block", [(32, 4), (24, 1), (16, 16)])
def test_operator_work_counts_the_pairs_the_mask_keeps(length, block):
    """``RingAttention``'s pairs are a brute-force count of the (2L, 2L)
    mask of the reference, each product once forward and twice backward at
    both widths; ``MoE`` is ``flops.moe_work`` over the 2 B L trunk rows."""
    import jax.numpy as jnp

    from benchmark.lib import flops

    builder = hx.config_module("configs", NAME)
    ref = hx.config_module("reference", NAME)
    mask = np.asarray(ref.diffusion_mask(
        {"block_length": block}, jnp.arange(2 * length), length))
    assert builder.kept_pairs(length, block) == int(mask.sum())
    cfg = dict(published(), buckets=[length], block_length=block)
    traffic = {"batch_size": 3}
    work = builder.operator_work(cfg, traffic)
    assert set(work) == {"MoE", "RingAttention"}
    assert work["RingAttention"] == {
        "flops": 4 * 3 * 2 * 3 * 32 * int(mask.sum()) * 256,
        "bytes": 4 * 2 * 2 * (2 * 3 * length) * (32 + 4) * 256}
    one = flops.moe_work(2 * 3 * length, H, 768, 128, cfg["num_experts"], 8)
    assert work["MoE"] == {k: 4 * v for k, v in one.items()}


def test_the_cell_and_its_metrics_are_in_the_benchmark_as_the_issue_gives():
    bench, cell, entry, cfg, traffic = hx.find_cell(CELL)
    assert cell["chips"] == 1 and traffic["driver"] == "bucketing_fit"
    assert cell["config"] == NAME and entry["reduced"] == CUT
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    assert entry["file"] == "benchmark/configs/sdar-30b-a3b.json"
    assert cell["traffic"] == traffic["name"] == "packed-8k-uniform-b1"
    assert traffic["length_mean"] == T and cfg["buckets"] == [T]
    assert traffic["batch_size"] == 1 and traffic["zipf_a"] == 0.0
    assert traffic["env"] == {"MXNET_BACKWARD_DO_MIRROR": "1"}
    assert traffic["reference_check"] == {"batch": 1, "seq_len": T}
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert NAME in [c["name"] for c in bench["configs"]]
    assert set(hx.metrics_of(bench, CELL, "end_to_end")) == {
        "train_tokens_per_s", "setup_s"}
    reported = hx.metrics_of(bench, CELL, "per_layer")
    for name in tuple(NEW) + (
            "attention.layers_per_step.seq",
            "attention.kernel_layers_per_step.seq",
            "attention.scored_pairs_per_step.seq", "attention_roofline.seq",
            "moe_roofline.seq", "moe.local_experts_per_step.seq",
            "moe.one_round_layers_per_step.seq", "kernels.mfu_pct.seq",
            "compile.window_compiles.seq", "device.peak_hbm_gib.seq",
            "device.idle_pct.seq", "dispatch.host_syncs_per_step.seq",
            "memory.step_scratch_gib.seq", "setup.trace_lower_s",
            "step.kept_residual_nodes_per_step.seq",
            "step.scoped_nodes_per_step.seq"):
        assert name in reported, name
    # every list that holds the Keye cell holds this one, but the
    # selection's three
    for m in bench["per_layer"] + bench["end_to_end"]:
        if KEYE in m.get("workloads", ()):
            assert (CELL in m["workloads"]) != (m["name"] in SELECTION), \
                m["name"]
    # the metrics this configuration brought are its cell's
    for name, (unit, better) in NEW.items():
        new = [m for m in bench["per_layer"] if m["name"] == name]
        assert len(new) == 1 and new[0]["workloads"] == [CELL]
        assert new[0]["moves"] == "train_tokens_per_s"
        assert new[0]["unit"] == unit and new[0]["better"] == better
        assert new[0]["source"] == "program_counter"
        assert new[0]["layer"] == "fused step"
        reader = hx.layer_readers()[name]
        assert (reader.UNIT, reader.BETTER, reader.MOVES) == (
            unit, better, "train_tokens_per_s")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bucketing_driver_runs_the_configuration(canned_trace, dtype):  # noqa: F811
    cfg, traffic = tiny()
    cfg["compute_dtype"] = dtype
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    run = run_driver(cfg, traffic, builder_of=NAME, seconds=0.3, trace=1,
                     bench=bench)
    tokens_a_step = run["obs"]["units"] / run["obs"]["steps"]
    assert 29 <= tokens_a_step <= 32           # a row of 29-32 clean tokens
    assert run["obs"]["program_syncs"] == 0
    # the reference and the builder agree at the small size (the noise of
    # the check is held to check_noise_seed on both sides); a bfloat16 trunk
    # at 64 features is off by more than TOLERANCES, which are set at
    # published widths on the chip
    assert run["correct"] or dtype == "bfloat16"
    assert run["failed"] == 0
    assert set(run["end_to_end"]) == {"train_tokens_per_s", "setup_s"}
    run["cell"] = dict(run["cell"], name=CELL)  # setup.* read their cell
    readers = hx.layer_readers()
    got = {n: readers[n].read(run)
           for n in hx.metrics_of(bench, CELL, "per_layer")}
    assert got["attention.diffusion_layers_per_step.seq"] == 2.0
    assert got["attention.layers_per_step.seq"] == 2.0
    assert got["attention.kernel_layers_per_step.seq"] == 0.0   # the CPU
    assert got["step.trunk_rows_per_token.seq"] == 2.0
    # one query block a walk at 32 positions: the clean copy's 32 x 32, the
    # noised copy's 32 x 28 of the clean one and 32 x 4 of its own
    assert got["attention.scored_per_kept_pair.seq"] == pytest.approx(
        (32 * 32 + 32 * 28 + 32 * 4) / (32 * 36))
    assert got["moe.local_experts_per_step.seq"] == 2 * 4
    assert got["dispatch.bucket_switches_per_step.seq"] == 0.0
    assert got["attention_roofline.seq"] is not None
    assert not [n for n, v in got.items() if v is None]
    # fresh noise every step: the probe differs from fence to fence
    # a program without the counters (the parent): 0, and no error
    run["obs"]["tm0"] = run["obs"]["tm1"] = {}
    for name in NEW:
        assert readers[name].read(run) == 0
