"""``mellum2-12b-a2.5b`` and its cell ``mellum2-12b-train-1c``: the
configuration file against the catalog's row and ``infer_shape``, the cell
and the metrics it is listed under, the builder's least work of
``RingAttention`` (three bands and one triangle, exactly), and the two layer
files this configuration brought, and the configuration at a tiny size
through the ``bucketing_fit`` driver here on the CPU (control flow and
counts only: nothing timed here is a device number). Every check is by
membership, never by a list's end: a later PR appends."""

import json
import os

import numpy as np
import pytest

from benchmark.lib import flops
from benchmark.lib import harness as hx
from benchmark.tests.test_drivers import canned_trace  # noqa: F401
from benchmark.tests.util import run_driver

CONFIG, CELL = "mellum2-12b-a2.5b", "mellum2-12b-train-1c"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "layer_types", "mlp_layer_types",
           "num_experts", "vocab_size"}
# the catalog row's ``config`` (JetBrains' config.json), for where the
# guide's file is not on the machine
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 7,
    "mlp_layer_types": ["sparse"] * 28,
}
LISTED_UNDER = (
    "moe.layers_per_step.seq", "moe.assignments_per_step.seq",
    "moe.kernel_matmuls_per_step.seq", "moe.local_experts_per_step.seq",
    "moe.one_round_layers_per_step.seq", "moe_roofline.seq",
    "attention.layers_per_step.seq", "attention.window_layers_per_step.seq",
    "attention.scored_pairs_per_step.seq",
    "attention.kernel_layers_per_step.seq", "attention_roofline.seq",
    "step.kept_residual_nodes_per_step.seq", "rotary.device_ms_per_step.seq",
    "rotary.kernel_nodes_per_step.seq", "rotary.scaled_nodes_per_step.seq",
    "attention.band_scored_per_kept_pair.seq", "kernels.mfu_pct.seq",
    "device.peak_hbm_gib.seq", "compile.window_compiles.seq",
    "setup.trace_lower_s", "compile.setup_compile_s")
NEW_LAYERS = {
    "rotary.scaled_nodes_per_step.seq": ("1/step", "higher"),
    "attention.band_scored_per_kept_pair.seq": ("ratio", "lower"),
}


@pytest.fixture(scope="module")
def cfg():
    return hx.load_json(hx.HERE, "configs", CONFIG + ".json")


def test_every_published_key_is_the_catalogs_or_listed_as_reduced(cfg):
    published = PUBLISHED
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = next(r for r in rows
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert row["config"] == PUBLISHED
        assert cfg["source"] == row["source_url"]
    changed = {k for k, v in published.items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == REDUCED
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) \
        == len(cfg["mlp_layer_types"]) == 4
    assert cfg["layer_types"] == published["layer_types"][:4]
    assert (cfg["num_experts"], cfg["num_experts_published"],
            cfg["expert_offset"]) == (8, 64, 0)
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert "8 chips share each layer" in cfg["deployment"]
    for key in ("qk_norm", "router_aux_loss_coef", "mtp_head", "parameters",
                "optimizer", "init", "precision", "batch"):
        assert key in cfg["assumed"], key
    for key in ("parameters", "buckets", "compute_dtype", "master_dtype",
                "optimizer"):
        assert key in cfg, key
    assert cfg["buckets"] == [16384]


def test_the_parameter_count_is_infer_shapes(cfg):
    import mxnet_tpu as mx

    builder = hx.config_module("configs", CONFIG)
    sym = builder.sym_gen(cfg, mx)[0](16384)[0]
    shapes = builder.input_shapes(cfg, 1, 16384)
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    count = sum(int(np.prod(s)) for n, s in zip(sym.list_arguments(),
                                                arg_shapes)
                if n not in shapes)
    assert count == cfg["parameters"] == 340350208


def test_the_cell_and_the_metrics_it_is_listed_under():
    bench, cell, entry, config, traffic = hx.find_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "packed-16k-uniform-b1", 1)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert set(entry["reduced"]) == REDUCED
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert traffic["reference_check"] == {"batch": 1, "seq_len": 16384}
    assert set(hx.metrics_of(bench, CELL, "end_to_end")) >= {
        "train_tokens_per_s", "setup_s"}
    per_layer = hx.metrics_of(bench, CELL, "per_layer")
    for name in LISTED_UNDER:
        assert name in per_layer, name
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert len(bench["workloads"]) >= 13 and four == ["resnet50-train-4c"]


def test_operator_work_is_three_bands_and_one_triangle(cfg):
    builder = hx.config_module("configs", CONFIG)
    traffic = hx.load_json(hx.HERE, "traffic", "packed-16k-uniform-b1.json")
    work = builder.operator_work(cfg, traffic)
    t, window, heads, d = 16384, 1024, 32, 128
    band = sum(min(i + 1, window) for i in range(t))
    triangle = t * (t + 1) // 2
    assert (band, triangle) == (16253440, 134225920)
    assert flops.causal_pairs(t, window) == band
    pairs = heads * (3 * band + triangle)
    assert work["RingAttention"]["flops"] == 3 * 2 * pairs * 2 * d
    assert work["RingAttention"]["bytes"] == 4 * 2 * 2 * t * 2 * d * (32 + 4)
    moe = flops.moe_work(t, 2304, 896, 64, 8, 8)
    assert work["MoE"] == flops.add_work(*[moe] * 4)
    # and the model FLOPs count the same pairs, a token
    macs = builder.forward_macs_per_token(cfg)
    attention = 2 * heads * d * (3 * band + triangle) / t
    rest = 4 * (2 * 2304 * 4096 + 2 * 2304 * 512 + 64 * 2304
                + 3 * 2304 * 896) + 2304 * 12288
    assert macs == pytest.approx(attention + rest, rel=1e-12)
    assert 0.35 < attention / macs < 0.45    # attention about 40% of a step


@pytest.mark.parametrize("name", sorted(NEW_LAYERS))
def test_layer_file_agrees_with_its_entry(name):
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    mod = hx.layer_readers()[name]
    unit, better = NEW_LAYERS[name]
    assert {k: entry[k] for k in ("unit", "layer", "moves", "better",
                                  "source")} == {
        "unit": mod.UNIT, "layer": mod.LAYER, "moves": mod.MOVES,
        "better": mod.BETTER, "source": mod.SOURCE} == {
        "unit": unit, "layer": "fused step", "moves": "train_tokens_per_s",
        "better": better, "source": "program_counter"}
    assert CELL in entry["workloads"]
    for cell in entry["workloads"]:
        assert "train_tokens_per_s" in hx.metrics_of(bench, cell,
                                                     "end_to_end")


def test_the_band_ratio_is_listed_in_every_cell_with_window_layers():
    """The counters it reads move wherever ``attention.window_layers`` does:
    this cell and the Trinity one."""
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    of = {m["name"]: m["workloads"] for m in bench["per_layer"]
          if "workloads" in m}
    assert sorted(of["attention.band_scored_per_kept_pair.seq"]) \
        == sorted(of["attention.window_layers_per_step.seq"])


def made_run(at_fence, at_end, steps=40):
    return {"obs": {"tm0": {"executor": dict(fused_plan_hit=4, **at_fence)},
                    "tm1": {"executor": dict(fused_plan_hit=44, **at_end)},
                    "steps": steps}}


def test_the_layer_files_read_the_cells_counters():
    readers = hx.layer_readers()
    kept, scored = 3 * 32 * 16253440, 3 * 32 * 20316160
    run = made_run(
        {"rotary_scaled_nodes": 2 * 4, "attention_band_kept_pairs": 4 * kept,
         "attention_band_scored_pairs": 4 * scored},
        {"rotary_scaled_nodes": 2 * 44,
         "attention_band_kept_pairs": 44 * kept,
         "attention_band_scored_pairs": 44 * scored})
    assert readers["rotary.scaled_nodes_per_step.seq"].read(run) == 2.0
    assert readers["attention.band_scored_per_kept_pair.seq"].read(run) \
        == pytest.approx(1.25, abs=1e-3)


@pytest.mark.parametrize("name", sorted(NEW_LAYERS))
@pytest.mark.parametrize("snapshots", [
    ({}, {}),
    # the parent of PR 62: the older counters, none of the new ones
    ({"rotary_nodes": 32, "attention_window_layers": 12},
     {"rotary_nodes": 352, "attention_window_layers": 132}),
], ids=["empty", "parent"])
def test_a_program_without_the_counters_reads_zero(name, snapshots):
    assert hx.layer_readers()[name].read(made_run(*snapshots)) == 0.0


def tiny():
    """The published file at widths of 16-64 and T 32, twice the 16
    positions its full layer's frequencies then start from."""
    cfg = hx.load_json(hx.HERE, "configs", CONFIG + ".json")
    rope = {kind: dict(entry, rope_theta=100)
            for kind, entry in cfg["rope_parameters"].items()}
    rope["full_attention"].update(
        factor=4, original_max_position_embeddings=16, beta_fast=1,
        beta_slow=0.1)
    cfg.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
               head_dim=16, moe_intermediate_size=32, num_experts=4,
               num_experts_published=16, expert_offset=4,
               num_experts_per_tok=2, vocab_size=64, sliding_window=8,
               rope_parameters=rope, buckets=[32], compute_dtype="float32")
    _, _, _, _, traffic = hx.find_cell(CELL)
    traffic.update(length_mean=32, length_std=1, batches_per_cycle=4,
                   min_slices=3, trace_steps=4,
                   reference_check={"batch": 1, "seq_len": 32})
    return cfg, traffic


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bucketing_driver_runs_the_configuration(canned_trace, dtype):  # noqa: F811
    cfg, traffic = tiny()
    cfg["compute_dtype"] = dtype
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    run = run_driver(cfg, traffic, builder_of=CONFIG, seconds=0.3, trace=1,
                     bench=bench)
    assert run["obs"]["program_syncs"] == 0 and run["failed"] == 0
    # the reference and the builder agree at the small size; a bfloat16
    # trunk at 64 features is off by more than TOLERANCES, which are set at
    # published widths on the chip
    assert run["correct"] or dtype == "bfloat16"
    assert set(run["end_to_end"]) == {"train_tokens_per_s", "setup_s"}
    run["cell"] = dict(run["cell"], name=CELL)  # setup.* read their cell
    readers = hx.layer_readers()
    got = {n: readers[n].read(run)
           for n in hx.metrics_of(bench, CELL, "per_layer")}
    assert got["attention.layers_per_step.seq"] == 4.0
    assert got["attention.window_layers_per_step.seq"] == 3.0
    assert got["attention.kernel_layers_per_step.seq"] == 0.0   # the CPU
    assert got["rotary.scaled_nodes_per_step.seq"] == 2.0
    assert got["rotary.kernel_nodes_per_step.seq"] == 0.0
    # one block of 32 positions holds the band of 8: 32 x 32 scored
    assert got["attention.band_scored_per_kept_pair.seq"] == pytest.approx(
        32 * 32 / sum(min(t + 1, 8) for t in range(32)))
    assert got["moe.layers_per_step.seq"] == 4.0
    assert got["moe.local_experts_per_step.seq"] == 4 * 4
    assert got["step.kept_residual_nodes_per_step.seq"] == 8.0
    assert got["dispatch.bucket_switches_per_step.seq"] == 0.0
    assert got["attention_roofline.seq"] is not None
    assert not [n for n, v in got.items() if v is None]
