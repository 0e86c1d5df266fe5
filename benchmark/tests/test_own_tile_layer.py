"""``attention.own_tile_layers_per_step.seq``: the layer file loads, agrees
with its entry in ``BENCHMARK.json`` and reads the program's counter over the
window's steps; a program on the ``jax.numpy`` squares, or one that has no
such counter (the parent of PR 61), reads 0 and does not raise."""

import pytest

from benchmark.lib import harness as hx

NAME = "attention.own_tile_layers_per_step.seq"
CELL = "sdar-30b-a3b-train-1c"


def made_run(at_fence, at_end, steps=48):
    return {"obs": {"tm0": {"executor": dict(fused_plan_hit=6, **at_fence)},
                    "tm1": {"executor": dict(fused_plan_hit=54, **at_end)},
                    "steps": steps}}


def test_layer_file_agrees_with_its_entry():
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    assert bench["per_layer"][-1]["name"] == NAME     # appended, last
    entry = bench["per_layer"][-1]
    mod = hx.layer_readers()[NAME]
    assert {k: entry[k] for k in ("unit", "layer", "moves", "better",
                                  "source")} == {
        "unit": mod.UNIT, "layer": mod.LAYER, "moves": mod.MOVES,
        "better": mod.BETTER, "source": mod.SOURCE} == {
        "unit": "1/step", "layer": "fused step",
        "moves": "train_tokens_per_s", "better": "higher",
        "source": "program_counter"}
    assert CELL in entry["workloads"]
    for cell in entry["workloads"]:
        assert NAME in hx.metrics_of(bench, cell, "per_layer")
        assert "train_tokens_per_s" in hx.metrics_of(bench, cell,
                                                     "end_to_end")
        # only a cell that runs the block-diffusion mask has an own tile
        assert "attention.diffusion_layers_per_step.seq" in hx.metrics_of(
            bench, cell, "per_layer")


@pytest.mark.parametrize("layers,warm_up,steps", [(4, 6, 60), (4, 0, 8)])
def test_reads_the_counter_over_the_windows_steps(layers, warm_up, steps):
    read = hx.layer_readers()[NAME].read
    run = made_run({"attention_own_tile_layers": layers * warm_up},
                   {"attention_own_tile_layers": layers * (warm_up + steps)},
                   steps)
    assert read(run) == float(layers)


@pytest.mark.parametrize("snapshots", [
    ({}, {}),
    # the squares: diffusion layers, none of them with an own tile
    ({"attention_diffusion_layers": 24, "attention_own_tile_layers": 0},
     {"attention_diffusion_layers": 264, "attention_own_tile_layers": 0}),
    # the parent: the other counters, not this one
    ({"attention_diffusion_layers": 24}, {"attention_diffusion_layers": 264}),
])
def test_the_squares_or_a_program_without_the_counter_read_zero(snapshots):
    read = hx.layer_readers()[NAME].read
    assert read(made_run(*snapshots)) == 0.0
