"""``conv.kernel_layers_per_step.seq``: the layer file loads, agrees with its
entry in ``BENCHMARK.json``, which lists the two cells whose models hold a
``CausalConv1D``, and reads the program's counter over the window's steps; a
program that has no such counter (the parent of the PR that brought it) reads
0 and does not raise."""

import pytest

from benchmark.lib import harness as hx

NAME = "conv.kernel_layers_per_step.seq"
CELLS = ["qwen3-next-train-1c", "zaya1-8b-train-1c"]


def made_run(at_fence, at_end, steps=48):
    return {"obs": {"tm0": {"executor": dict(fused_plan_hit=6, **at_fence)},
                    "tm1": {"executor": dict(fused_plan_hit=54, **at_end)},
                    "steps": steps}}


def test_layer_file_agrees_with_its_entry():
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    mod = hx.layer_readers()[NAME]
    assert mod.NAME == NAME
    assert {k: entry[k] for k in ("unit", "layer", "moves", "better",
                                  "source")} == {
        "unit": mod.UNIT, "layer": mod.LAYER, "moves": mod.MOVES,
        "better": mod.BETTER, "source": mod.SOURCE} == {
        "unit": "1/step", "layer": "fused step",
        "moves": "train_tokens_per_s", "better": "higher",
        "source": "program_counter"}
    # a later PR may append cells: nothing here pins the list
    assert set(CELLS) <= set(entry["workloads"])
    for cell in entry["workloads"]:
        assert NAME in hx.metrics_of(bench, cell, "per_layer")
        assert "train_tokens_per_s" in hx.metrics_of(bench, cell,
                                                     "end_to_end")


@pytest.mark.parametrize("layers,warm_up,steps", [
    (3, 6, 48),    # the Qwen3-Next cell: three Gated DeltaNet layers a step
    (4, 6, 48),    # ZAYA1's four first convolutions, at a batch the rule takes
    (36, 0, 16),   # Qwen3-Next's published depth
])
def test_reads_the_counter_over_the_windows_steps(layers, warm_up, steps):
    read = hx.layer_readers()[NAME].read
    run = made_run({"conv_kernel_layers": layers * warm_up},
                   {"conv_kernel_layers": layers * (warm_up + steps)}, steps)
    assert read(run) == float(layers)


@pytest.mark.parametrize("snapshots", [
    ({}, {}),
    ({"conv_grouped_layers": 24}, {"conv_grouped_layers": 216}),
])
def test_a_program_on_the_fall_back_or_without_the_counter_reads_zero(
        snapshots):
    read = hx.layer_readers()[NAME].read
    assert read(made_run(*snapshots)) == 0.0
