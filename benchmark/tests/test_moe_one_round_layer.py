"""``moe.one_round_layers_per_step.seq``: the layer file loads, agrees with
its entry in ``BENCHMARK.json``, which lists the seven cells that hold a
``MoE`` node, and reads the program's counter over the window's steps; a
program that has no such counter (the parent of the PR that brought it)
reads 0 and does not raise."""

import pytest

from benchmark.lib import harness as hx

NAME = "moe.one_round_layers_per_step.seq"
CELLS = ["olmoe-1b7b-train-1c", "trinity-mini-train-1c",
         "qwen3-next-train-1c", "kanana2-30b-train-1c", "zaya1-8b-train-1c",
         "kimi-linear-train-1c", "keye-vl2-30b-train-1c"]


def made_run(at_fence, at_end, steps=48):
    return {"obs": {"tm0": {"executor": dict(fused_plan_hit=6, **at_fence)},
                    "tm1": {"executor": dict(fused_plan_hit=54, **at_end)},
                    "steps": steps}}


def test_layer_file_agrees_with_its_entry():
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    mod = hx.layer_readers()[NAME]
    assert {k: entry[k] for k in ("unit", "layer", "moves", "better",
                                  "source")} == {
        "unit": mod.UNIT, "layer": mod.LAYER, "moves": mod.MOVES,
        "better": mod.BETTER, "source": mod.SOURCE} == {
        "unit": "1/step", "layer": "fused step",
        "moves": "train_tokens_per_s", "better": "higher",
        "source": "program_counter"}
    # a later PR may append cells: nothing here pins the list
    assert set(CELLS) <= set(entry["workloads"])
    layers = next(m for m in bench["per_layer"]
                  if m["name"] == "moe.layers_per_step.seq")
    assert set(entry["workloads"]) <= set(layers["workloads"])
    for cell in entry["workloads"]:
        assert NAME in hx.metrics_of(bench, cell, "per_layer")
        assert "train_tokens_per_s" in hx.metrics_of(bench, cell,
                                                     "end_to_end")


@pytest.mark.parametrize("layers,warm_up,steps", [
    (4, 8, 160),   # the ZAYA1 cell: 8 of 16 experts at top-1, four layers
    (1, 8, 280),   # the OLMoE cell: every expert held
    (0, 8, 200),   # a held range of several rounds
])
def test_reads_the_counter_over_the_windows_steps(layers, warm_up, steps):
    read = hx.layer_readers()[NAME].read
    run = made_run({"moe_one_round_layers": layers * warm_up, "moe_layers": 4},
                   {"moe_one_round_layers": layers * (warm_up + steps),
                    "moe_layers": 4}, steps)
    assert read(run) == float(layers)


def test_a_program_without_the_counter_reads_zero():
    read = hx.layer_readers()[NAME].read
    assert read(made_run({"moe_layers": 32}, {"moe_layers": 672})) == 0.0
