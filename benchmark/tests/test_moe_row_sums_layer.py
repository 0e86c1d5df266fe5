"""``moe.kernel_row_sums_per_step.seq``: the layer file loads, agrees with
its entry in ``BENCHMARK.json``, which lists the eight cells whose ``MoE``
holds a range of the experts (the cells with a scatter-add to replace: the
cell that holds every expert is not among them), and reads the program's
counter over the window's steps; a program that has no such counter (the
parent of the PR that brought it) reads 0 and does not raise."""

import pytest

from benchmark.lib import harness as hx

NAME = "moe.kernel_row_sums_per_step.seq"
CELLS = ["trinity-mini-train-1c", "qwen3-next-train-1c",
         "kanana2-30b-train-1c", "zaya1-8b-train-1c", "kimi-linear-train-1c",
         "keye-vl2-30b-train-1c", "sdar-30b-a3b-train-1c",
         "mellum2-12b-train-1c"]


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
    # a later PR may append cells, and entries after this one: nothing here
    # pins the list or the entry's place in ``per_layer``
    assert set(CELLS) <= set(entry["workloads"])
    held = next(m for m in bench["per_layer"]
                if m["name"] == "moe.local_experts_per_step.seq")
    assert set(entry["workloads"]) <= set(held["workloads"])
    for cell in entry["workloads"]:
        assert NAME in hx.metrics_of(bench, cell, "per_layer")
        assert "train_tokens_per_s" in hx.metrics_of(bench, cell,
                                                     "end_to_end")


@pytest.mark.parametrize("sums,warm_up,steps", [
    (8, 8, 72),    # the Mellum2 cell: four layers, rounds of 32 768 rows
    (8, 8, 144),   # the SDAR cell
    (0, 8, 280),   # rounds the rule leaves to XLA's scatter
])
def test_reads_the_counter_over_the_windows_steps(sums, warm_up, steps):
    read = hx.layer_readers()[NAME].read
    run = made_run({"moe_kernel_row_sums": sums * warm_up, "moe_layers": 4},
                   {"moe_kernel_row_sums": sums * (warm_up + steps),
                    "moe_layers": 4}, steps)
    assert read(run) == float(sums)


def test_a_program_without_the_counter_reads_zero():
    read = hx.layer_readers()[NAME].read
    assert read(made_run({"moe_layers": 32}, {"moe_layers": 672})) == 0.0
