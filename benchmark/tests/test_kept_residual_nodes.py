"""``step.kept_residual_nodes_per_step.seq``: the layer file loads, agrees
with its entry in ``BENCHMARK.json``, which lists the two cells that run
under per-operator recomputation, and reads the program's counter over the
window's steps; a program that has no such counter (the parent of the PR
that brought it, or a cell with the switch off) reads 0 and does not
raise."""

import pytest

from benchmark.lib import harness as hx

NAME = "step.kept_residual_nodes_per_step.seq"
CELLS = ["trinity-mini-train-1c", "qwen3-next-train-1c"]


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
    for cell in entry["workloads"]:
        assert NAME in hx.metrics_of(bench, cell, "per_layer")
        assert "train_tokens_per_s" in hx.metrics_of(bench, cell,
                                                     "end_to_end")
        # the cells whose traffic sets the switch the counter counts under
        traffic = hx.find_cell(cell)[4]
        assert traffic["env"]["MXNET_BACKWARD_DO_MIRROR"] == "1"


@pytest.mark.parametrize("nodes,warm_up,steps", [
    (8, 8, 80),    # the Qwen3-Next cell: 3 linear + 1 attention + 4 MoE
    (9, 8, 80),    # the Trinity cell: 5 attention + 4 MoE
    (9, 0, 16),
])
def test_reads_the_counter_over_the_windows_steps(nodes, warm_up, steps):
    read = hx.layer_readers()[NAME].read
    run = made_run({"kept_residual_nodes": nodes * warm_up},
                   {"kept_residual_nodes": nodes * (warm_up + steps)}, steps)
    assert read(run) == float(nodes)


@pytest.mark.parametrize("snapshots", [
    ({}, {}),
    ({"scoped_nodes": 1512}, {"scoped_nodes": 16632}),
])
def test_a_program_that_keeps_nothing_or_has_no_counter_reads_zero(snapshots):
    read = hx.layer_readers()[NAME].read
    assert read(made_run(*snapshots)) == 0.0
