"""``phi-4-mini-flash`` and its cell ``phi4-mini-flash-train-1c``: the
configuration file against the catalog's row and ``infer_shape``, the cell
and the metrics it is listed under, the builder's least work against a hand
count, the seven layer files this configuration brought, and the
configuration at a tiny size through the ``bucketing_fit`` driver here on
the CPU (control flow and counts only: nothing timed here is a device
number). Every check is by membership, never by a list's end: a later PR
appends."""

import json
import os

import numpy as np
import pytest

from benchmark.lib import flops
from benchmark.lib import harness as hx
from benchmark.tests.test_drivers import canned_trace  # noqa: F401
from benchmark.tests.util import run_driver

CONFIG, CELL = "phi-4-mini-flash", "phi4-mini-flash-train-1c"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "layer_kinds", "vocab_size"}
# the catalog row's ``config`` (Microsoft's config.json), for where the
# guide's file is not on the machine
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064,
}
KINDS = ["mamba", "window", "mamba_memory", "full_shared", "gmu", "cross"]
LISTED_UNDER = (
    "attention.layers_per_step.seq", "attention.window_layers_per_step.seq",
    "attention.scored_pairs_per_step.seq",
    "attention.kernel_layers_per_step.seq", "attention_roofline.seq",
    "attention.band_scored_per_kept_pair.seq", "attention.lanes_per_pair.seq",
    "causal_conv_roofline.seq", "conv.kernel_layers_per_step.seq",
    "step.kept_residual_nodes_per_step.seq", "kernels.mfu_pct.seq",
    "device.peak_hbm_gib.seq", "device.idle_pct.seq",
    "memory.step_arguments_gib.seq", "compile.window_compiles.seq",
    "dispatch.host_ms_per_step.seq", "loop.step_ms.seq",
    "input.pad_waste_pct.seq", "setup.trace_lower_s",
    "compile.setup_compile_s")
NOT_LISTED_UNDER = ("moe.layers_per_step.seq", "moe_roofline.seq",
                    "rotary.kernel_nodes_per_step.seq",
                    "linear_attention.layers_per_step.seq")
COUNTERS = {
    "state_space.layers_per_step.seq": "selective_scan_layers",
    "state_space.kernel_layers_per_step.seq": "selective_scan_kernel_layers",
    "state_space.state_updates_per_step.seq": "selective_scan_state_updates",
}
# counted from the symbol the cell binds (the builder's ``graph_counts``)
GRAPH_COUNTS = {
    "attention.shared_kv_layers_per_step.seq": "shared_kv_layers",
    "attention.differential_layers_per_step.seq": "differential_layers",
    "state_space.memory_gate_layers_per_step.seq": "memory_gate_layers",
}
NEW_LAYERS = dict({n: ("1/step", "fused step", "program_counter")
                   for n in (*COUNTERS, *GRAPH_COUNTS)},
                  **{"selective_scan_roofline.seq":
                     ("%", "kernels", "device_trace")})


@pytest.fixture(scope="module")
def cfg():
    return hx.load_json(hx.HERE, "configs", CONFIG + ".json")


def test_every_published_key_is_the_catalogs_or_listed_as_reduced(cfg):
    published = dict(PUBLISHED)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = next(r for r in rows
                   if r["name"] == "Phi-4-mini-flash-reasoning")
        assert row["config"] == PUBLISHED
        assert cfg["source"] == row["source_url"]
    # the family's rule gives the published kinds; config.json has no list
    from mxnet_tpu.models import phi4flash

    published["layer_kinds"] = list(phi4flash.PUBLISHED_KINDS)
    changed = {k for k, v in published.items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == REDUCED
    # no width among them, nor among the further keys
    assert not [k for k in cfg["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_expand"],
            cfg["mamba_dt_rank"]) == (16, 4, 2, -(-2560 // 16))
    assert cfg["num_hidden_layers"] == len(cfg["layer_kinds"]) \
        == len(cfg["layer_ids"]) == 6
    assert cfg["layer_kinds"] == KINDS \
        == [phi4flash.PUBLISHED_KINDS[i] for i in cfg["layer_ids"]]
    assert cfg["layer_ids"] == [0, 1, 16, 17, 18, 19]
    assert (cfg["num_hidden_layers_published"],
            cfg["vocab_size_published"]) == (32, 200064)
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert "vocabulary-parallel over 8 chips" in cfg["deployment"]
    for key in ("mamba_sizes", "attention_bias", "head_pairs", "lambda",
                "subln", "scan_init", "parameters", "optimizer", "init",
                "precision", "batch"):
        assert key in cfg["assumed"], key
    for key in ("parameters", "buckets", "compute_dtype", "master_dtype",
                "optimizer"):
        assert key in cfg, key
    assert cfg["buckets"] == [4096]


def test_the_published_kinds_are_the_familys_rule():
    from mxnet_tpu.models import phi4flash

    kinds = phi4flash.PUBLISHED_KINDS
    assert len(kinds) == 32
    assert [k for k in kinds[:16:2]] == ["mamba"] * 8
    assert [k for k in kinds[1:16:2]] == ["window"] * 8
    assert kinds[16:18] == ("mamba_memory", "full_shared")
    assert kinds[18::2] == ("gmu",) * 7 and kinds[19::2] == ("cross",) * 7


def _count(cfg, seq_len):
    import mxnet_tpu as mx

    builder = hx.config_module("configs", CONFIG)
    sym = builder.sym_gen(cfg, mx)[0](seq_len)[0]
    shapes = builder.input_shapes(cfg, 1, seq_len)
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    return sum(int(np.prod(s)) for n, s in zip(sym.list_arguments(),
                                               arg_shapes) if n not in shapes)


def test_the_parameter_count_is_infer_shapes(cfg):
    biases = 2 * (5120 + 2560) + 2 * 2560
    assert _count(cfg, 4096) == cfg["parameters"] == 697073792 + biases
    # and the uncut model is the published 3.8B
    from mxnet_tpu.models import phi4flash

    uncut = dict(cfg, layer_kinds=list(phi4flash.PUBLISHED_KINDS),
                 layer_ids=list(range(32)), vocab_size=200064)
    assert _count(uncut, 64) == 3852457984 + 9 * 7680 + 7 * 5120


def test_the_cell_and_the_metrics_it_is_listed_under():
    bench, cell, entry, config, traffic = hx.find_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "packed-4k-uniform-b1", 1)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert set(entry["reduced"]) == REDUCED
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert (traffic["driver"], traffic["batch_size"], traffic["length_mean"],
            traffic["env"]) == ("bucketing_fit", 1, 4096,
                                {"MXNET_BACKWARD_DO_MIRROR": "1"})
    assert traffic["reference_check"] == {"batch": 1, "seq_len": 4096}
    assert set(hx.metrics_of(bench, CELL, "end_to_end")) >= {
        "train_tokens_per_s", "setup_s"}
    per_layer = hx.metrics_of(bench, CELL, "per_layer")
    for name in LISTED_UNDER + tuple(NEW_LAYERS):
        assert name in per_layer, name
    for name in NOT_LISTED_UNDER:
        assert name not in per_layer, name
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert len(bench["workloads"]) >= 14 and four == ["resnet50-train-4c"]


def test_operator_work_is_a_hand_count(cfg):
    builder = hx.config_module("configs", CONFIG)
    traffic = hx.load_json(hx.HERE, "traffic", "packed-4k-uniform-b1.json")
    work = builder.operator_work(cfg, traffic)
    t, window = 4096, 512
    band = sum(min(i + 1, window) for i in range(t))
    triangle = t * (t + 1) // 2
    assert flops.causal_pairs(t, window) == band
    # two nodes a differential layer, 20 query heads each: q.k over 64, p.v
    # over 128; a band and two triangles
    pairs = 2 * 20 * (band + 2 * triangle)
    assert work["RingAttention"]["flops"] == 3 * 2 * pairs * (64 + 128)
    assert work["RingAttention"]["bytes"] \
        == 6 * 2 * 2 * t * (20 + 10) * (64 + 128)
    c, n = 5120, 16
    assert work["SelectiveScan"]["flops"] == 2 * 27 * t * c * n
    assert work["SelectiveScan"]["bytes"] \
        == 2 * (3 * 2 * t * (3 * c + 2 * n) + 2 * 4 * c * (n + 2))
    assert work["CausalConv1D"]["flops"] == 2 * 3 * 2 * t * c * 4
    assert work["CausalConv1D"]["bytes"] \
        == 2 * (2 * 2 * t * 2 * c + 2 * 4 * c * 5)
    # the scan's count is bound by bytes on a v5e
    peaks = hx.load_json(hx.HERE, "peaks.json")["devices"]["TPU v5 lite"]
    scan = work["SelectiveScan"]
    assert scan["bytes"] / (peaks["hbm_gb_per_s"] * 1e9) \
        > scan["flops"] / (peaks["bf16_tflops"] * 1e12)
    # and the model FLOPs, a token: what the cell's ``why`` says
    macs = builder.forward_macs_per_token(cfg)
    mlp = 6 * 3 * 2560 * 10240
    head = 2560 * 25008
    attention = 2 * 20 * (band + 2 * triangle) / t * (64 + 128)
    scans = 2 * 3 * 16 * 5120
    projections = 2 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560) \
        + 2 * (2560 * 5120 + 2560 * 2560) + 2 * 2560 * 5120 \
        + 2 * 2560 * 2560
    conv = 2 * 4 * 5120
    assert macs == pytest.approx(
        mlp + head + attention + scans + projections + conv, rel=1e-12)
    assert 0.60 < mlp / macs < 0.70 and 0.08 < head / macs < 0.10
    assert 0.03 < attention / macs < 0.06 and scans / macs < 0.002
    assert builder.train_flops_per_unit(cfg) == pytest.approx(6 * macs)
    assert 4.2e9 < builder.train_flops_per_unit(cfg) < 4.6e9


@pytest.mark.parametrize("name", sorted(NEW_LAYERS))
def test_layer_file_agrees_with_its_entry(name):
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    mod = hx.layer_readers()[name]
    unit, layer, source = NEW_LAYERS[name]
    assert {k: entry[k] for k in ("unit", "layer", "moves", "better",
                                  "source")} == {
        "unit": mod.UNIT, "layer": mod.LAYER, "moves": mod.MOVES,
        "better": mod.BETTER, "source": mod.SOURCE} == {
        "unit": unit, "layer": layer, "moves": "train_tokens_per_s",
        "better": "higher", "source": source}
    assert CELL in entry["workloads"]
    for cell in entry["workloads"]:
        assert "train_tokens_per_s" in hx.metrics_of(bench, cell,
                                                     "end_to_end")


def made_run(at_fence, at_end, steps=40):
    return {"obs": {"tm0": {"executor": dict(fused_plan_hit=4, **at_fence)},
                    "tm1": {"executor": dict(fused_plan_hit=44, **at_end)},
                    "steps": steps}}


def test_the_layer_files_read_the_cells_counters():
    readers = hx.layer_readers()
    a_step = {"selective_scan_layers": 2, "selective_scan_kernel_layers": 2,
              "selective_scan_state_updates": 2 * 4096 * 5120 * 16}
    run = made_run({k: 4 * v for k, v in a_step.items()},
                   {k: 44 * v for k, v in a_step.items()})
    for name, counter in COUNTERS.items():
        assert readers[name].read(run) == a_step[counter], name


@pytest.mark.parametrize("name", sorted(COUNTERS))
@pytest.mark.parametrize("snapshots", [
    ({}, {}),
    # the parent of PR 65: the older counters, none of the new ones
    ({"attention_layers": 24, "conv_kernel_layers": 8},
     {"attention_layers": 264, "conv_kernel_layers": 88}),
], ids=["empty", "parent"])
def test_a_program_without_the_counters_reads_zero(name, snapshots):
    assert hx.layer_readers()[name].read(made_run(*snapshots)) == 0.0


@pytest.mark.parametrize("kinds, want", [
    (KINDS, (3, 1, 1)),
    # two readers of each shared tensor; no reader at all
    (KINDS + ["gmu", "cross"], (4, 2, 2)),
    (["mamba", "window", "mamba_memory", "full_shared"], (2, 0, 0)),
], ids=["the-cell", "two-readers", "no-reader"])
def test_the_graph_counts_are_the_symbols(cfg, kinds, want):
    """``graph_counts`` and the three layer files that read it, at the
    published widths (a symbol: nothing is bound): differential layers,
    readers of another layer's keys and values, Gated Memory Units. Another
    configuration's builder has no such count: the files read nothing."""
    import mxnet_tpu as mx

    cfg = dict(cfg, layer_kinds=kinds, layer_ids=list(range(len(kinds))))
    run = {"builder": hx.config_module("configs", CONFIG), "config": cfg,
           "mx": mx}
    readers = hx.layer_readers()
    names = ("attention.differential_layers_per_step.seq",
             "attention.shared_kv_layers_per_step.seq",
             "state_space.memory_gate_layers_per_step.seq")
    assert tuple(readers[n].read(run) for n in names) == want
    other = dict(run, builder=hx.config_module("configs", "olmoe-1b-7b"))
    assert [readers[n].read(other) for n in names] == [None] * 3


def tiny():
    """The published file at widths of 16-128, a band of 8 and T 64."""
    cfg = hx.load_json(hx.HERE, "configs", CONFIG + ".json")
    cfg.update(hidden_size=64, intermediate_size=96, num_attention_heads=4,
               num_key_value_heads=2, sliding_window=8, mamba_dt_rank=4,
               vocab_size=64, buckets=[64], compute_dtype="float32")
    _, _, _, _, traffic = hx.find_cell(CELL)
    traffic.update(length_mean=64, length_std=1, batches_per_cycle=4,
                   min_slices=3, trace_steps=4,
                   reference_check={"batch": 1, "seq_len": 64})
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
    assert got["state_space.layers_per_step.seq"] == 2.0
    assert got["state_space.kernel_layers_per_step.seq"] == 0.0   # the CPU
    assert got["state_space.state_updates_per_step.seq"] \
        == 2 * 64 * 128 * 16
    assert got["state_space.memory_gate_layers_per_step.seq"] == 1.0
    assert got["attention.shared_kv_layers_per_step.seq"] == 1.0
    assert got["attention.differential_layers_per_step.seq"] == 3.0
    assert got["attention.layers_per_step.seq"] == 6.0
    assert got["attention.window_layers_per_step.seq"] == 2.0
    assert got["attention.kernel_layers_per_step.seq"] == 0.0   # the CPU
    assert got["attention.lanes_per_pair.seq"] == 16 + 32
    assert got["conv.kernel_layers_per_step.seq"] == 0.0
    # one block of 64 positions holds the band of 8: 64 x 64 scored
    assert got["attention.band_scored_per_kept_pair.seq"] == pytest.approx(
        64 * 64 / sum(min(t + 1, 8) for t in range(64)))
    assert got["dispatch.bucket_switches_per_step.seq"] == 0.0
    # the canned trace has a RingAttention row and none of the other two
    # operators': their shares are left out, not raised
    assert got["attention_roofline.seq"] is not None
    assert sorted(n for n, v in got.items() if v is None) == [
        "causal_conv_roofline.seq", "selective_scan_roofline.seq"]


def test_the_scans_share_reads_its_row_of_the_table(cfg):
    """``selective_scan_roofline.seq`` over a made table: 20 ms a step under
    the operator's name against the builder's count, bound by bytes."""
    builder = hx.config_module("configs", CONFIG)
    traffic = hx.load_json(hx.HERE, "traffic", "packed-4k-uniform-b1.json")
    work = builder.operator_work(cfg, traffic)["SelectiveScan"]
    run = {"builder": builder, "config": cfg, "traffic": traffic,
           "obs": {"trace_slice": (8,), "peak_flops": 197e12,
                   "peak_bytes_per_s": 819e9,
                   "trace": {"table": {"unscoped_share": 0.0, "by_operator": [
                       {"operator": "SelectiveScan", "pass": "forward",
                        "ms": 40.0},
                       {"operator": "SelectiveScan", "pass": "backward",
                        "ms": 120.0},
                       {"operator": "FullyConnected", "pass": "forward",
                        "ms": 500.0}]}}}}
    got = hx.layer_readers()["selective_scan_roofline.seq"].read(run)
    assert got == pytest.approx(100 * (work["bytes"] / 819e9) / 0.020)
    assert 0 < got < 100
