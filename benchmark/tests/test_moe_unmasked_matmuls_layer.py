"""``moe.unmasked_matmuls_per_step.seq``: the layer file loads, agrees with
its entry in ``BENCHMARK.json``, which lists the six cells whose ``MoE``
holds a range of the experts and whose rate rose when the masks went (the
counter reads 36.0 in the Kimi-Linear and Keye-VL-2.0 cells too, whose rates
fell by 0.4% and did not move: they are not listed under a metric that says
it moves the rate up; the cell that holds every expert has no dead row), and
reads the program's counter over the window's steps; a program that has no
such counter (the parent of the PR that brought it) reads 0 and does not
raise."""

import pytest

from benchmark.lib import harness as hx

NAME = "moe.unmasked_matmuls_per_step.seq"
CELLS = ["trinity-mini-train-1c", "qwen3-next-train-1c",
         "kanana2-30b-train-1c", "zaya1-8b-train-1c", "sdar-30b-a3b-train-1c",
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
    sums = next(m for m in bench["per_layer"]
                if m["name"] == "moe.kernel_row_sums_per_step.seq")
    assert set(entry["workloads"]) <= set(sums["workloads"])
    for cell in entry["workloads"]:
        assert NAME in hx.metrics_of(bench, cell, "per_layer")
        assert "train_tokens_per_s" in hx.metrics_of(bench, cell,
                                                     "end_to_end")


@pytest.mark.parametrize("matmuls,warm_up,steps", [
    (36, 8, 72),    # the Mellum2 cell: four held-range layers, nine each
    (36, 8, 280),   # the ZAYA1 cell: one round a layer
    (0, 8, 144),    # rounds whose matmuls keep their masks
])
def test_reads_the_counter_over_the_windows_steps(matmuls, warm_up, steps):
    read = hx.layer_readers()[NAME].read
    run = made_run(
        {"moe_unmasked_matmuls": matmuls * warm_up, "moe_layers": 4},
        {"moe_unmasked_matmuls": matmuls * (warm_up + steps),
         "moe_layers": 4}, steps)
    assert read(run) == float(matmuls)


def test_a_program_without_the_counter_reads_zero():
    read = hx.layer_readers()[NAME].read
    assert read(made_run({"moe_layers": 32}, {"moe_layers": 672})) == 0.0
